"""
The fleet trainer: thousands of per-machine models as one stacked,
vmapped, mesh-sharded computation.

This is the TPU-native replacement for the reference's scale axis — one
Argo-scheduled k8s pod per model build
(argo-workflow.yml.template:1519-1598). Here the fleet becomes:

1. **Bucketing** — machines are grouped by (ModelSpec, FitConfig, padded
   shape). Specs are frozen dataclasses, so each distinct architecture
   geometry compiles exactly once regardless of fleet size (no retrace
   storms).
2. **Stacking** — each bucket's data becomes ``X[M, N, ...]`` with weight
   masks expressing ragged lengths, validation splits and CV-fold
   boundaries (masks are *data*, so per-machine differences never cause
   recompilation).
3. **vmap + GSPMD** — the single-model fused fit program
   (models/training.py: one jitted scan over epochs×batches) is vmapped
   over the model axis and sharded over a ``(models, data)`` mesh;
   training M models is a single device program. The model axis needs no
   collectives; sharding the sample axis makes XLA insert gradient psums
   over ``data``.

RNG: each member trains with its own fold of a PRNG key, so fleet results
are independent of bucket composition and deterministic per seed.
"""

import logging
import time
from dataclasses import dataclass, replace
from functools import lru_cache
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.errors import JaxRuntimeError
from jax.sharding import Mesh

from .. import telemetry
from ..models.anomaly.diff import THRESHOLD_RUN
from ..models.in_flight import Flight, LeafInFlight
from ..models.nn import forward_fn_for, init_fn_for
from ..models.spec import ModelSpec
from ..models.training import (
    FitConfig,
    History,
    build_raw_fit_fn,
    shuffle_columns,
    validation_inputs,
)
from ..planner.costmodel import (
    CostModel,
    spec_flops_per_sample,
    spec_param_count,
)
# _round_up_pow2 (the historical dense pad target) now lives in the
# planner — the naive strategy is its one implementation; re-exported
# here for the long-standing import path.
from ..planner.packing import (  # noqa: F401
    _round_up_pow2,
    naive_pad_target,
    plan_train_buckets,
)
from ..utils.faults import InjectedDeviceError, fault_point
from . import host_blocks
from .mesh import make_mesh, model_data_sharding, model_sharding

logger = logging.getLogger(__name__)

def is_device_error(exc: BaseException) -> bool:
    """True for failures raised BY a device program — XLA runtime errors
    (``RESOURCE_EXHAUSTED`` OOMs, preempted/poisoned device programs) and
    their injected test stand-ins. These are the failures worth bucket
    bisection: the bucket may simply be over-packed, or one member's
    geometry may be poisonous, and retrying halves isolates which.
    Host-side errors (bad config, data bugs) are deterministic and are
    NOT classified as device errors."""
    if isinstance(exc, (InjectedDeviceError, JaxRuntimeError)):
        return True
    return "RESOURCE_EXHAUSTED" in str(exc)


@dataclass
class FleetMember:
    """One machine's training problem, already staged as arrays."""

    name: str
    spec: ModelSpec
    X: np.ndarray  # [n, ...features]
    y: np.ndarray  # [n, n_features_out]
    train_weights: Optional[np.ndarray] = None  # defaults to all rows
    val_weights: Optional[np.ndarray] = None
    seed: int = 42

    def __post_init__(self):
        if len(self.X) != len(self.y):
            raise ValueError(
                f"{self.name}: X ({len(self.X)}) and y ({len(self.y)}) lengths differ"
            )

    @property
    def n(self) -> int:
        return len(self.X)


@dataclass
class WindowedFleetMember:
    """
    One windowed (LSTM) machine's training problem as the RAW series plus
    window bookkeeping — windows are gathered on device per batch
    (models/training.py build_raw_windowed_fit_fn), so fleet HBM holds
    ``[n, F]`` per member instead of the ``lookback×`` window blowup.
    """

    name: str
    spec: ModelSpec  # an LSTMSpec (carries lookback_window)
    series: np.ndarray  # [n, F] raw input series
    targets: np.ndarray  # [n_windows, F_out] via ops.windows.window_targets
    order: Optional[np.ndarray] = None  # virtual slot -> window start; None=arange
    train_weights: Optional[np.ndarray] = None  # per virtual slot
    val_weights: Optional[np.ndarray] = None
    seed: int = 42

    def __post_init__(self):
        lookback = self.spec.lookback_window
        # Validate on the window count (targets length), not raw series
        # length: lookahead shortens the window set too, and zero windows
        # would otherwise train nothing yet report a clean 0.0-loss history.
        if len(self.targets) < 1:
            raise ValueError(
                f"{self.name}: series of {len(self.series)} rows too short "
                f"for lookback {lookback} (no complete windows)"
            )

    @property
    def n_windows(self) -> int:
        return len(self.targets)


@dataclass
class FleetResult:
    name: str
    #: host numpy pytree; None when ``error`` is set, and when the fit
    #: left its parameters on the device (``block``). Of a large
    #: artifact the leaves are on their way (``in_flight``)
    params: Any
    history: History
    seed: int = 0  # the RNG seed this member actually trained with
    retries: int = 0  # diverged-member reseed retries that led to this result
    #: set when this member's device program failed in ISOLATION after
    #: bucket bisection — the member trained nothing; callers decide the
    #: degradation policy (FleetBuilder falls back to the sequential
    #: ModelBuilder path)
    error: Optional[BaseException] = None
    #: of a fit whose parameters stayed on the device
    #: (``FleetTrainer.train(params_on_device=True)``): its bucket's
    #: stacked parameters as the fit program returned them (padded,
    #: sharded over ``models``; every member of the bucket refers to the
    #: same tree) and this member's ``row`` in them. Nothing fetches
    #: them: ``FleetTrainer.device_params`` hands them to a predict
    #: program, and the device's memory is theirs until the last result
    #: that refers to them goes.
    block: Any = None
    row: int = 0
    #: of a fit that did not wait for its parameters (``defers``): the
    #: transfers it started, whose leaves ``params`` holds
    #: (models/in_flight.py); None on the eager schedule
    in_flight: Optional[Flight] = None


@dataclass
class FoldScoring:
    """What the scoring half of a predict program reads, stacked over a
    bucket's fold models (:func:`fold_scores` has the arithmetic). A
    scaler is its fitted transform a tag, ``(shift, mul, div, add)``:
    ``((x - shift) * mul) / div + add``."""

    y_true: np.ndarray  # [M, N, T] targets of the rows predicted
    rows: np.ndarray  # [M] rows that count; the rest of a block is padding
    metric_scaler: np.ndarray  # [M, 4, T] the machine's scoring scaler
    error_scaler: np.ndarray  # [M, 4, T] the detector's, fitted to the fold's training rows
    window: Optional[int] = None  # the detectors' smoothing window (static)

    @property
    def nbytes(self) -> int:
        return tree_nbytes(
            self.y_true, self.rows, self.metric_scaler, self.error_scaler
        )


def tree_nbytes(*trees, on_host: bool = False) -> int:
    """Bytes of the arrays in ``trees`` (a None holds none): what a
    ``build_part`` span says it moved. ``on_host``: of the leaves that
    are not on a device yet, which is what a transfer moves."""
    return sum(
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(trees)
        if not (on_host and isinstance(leaf, jax.Array))
    )


def _fetch_for(span, tree):
    """:func:`fetch_to_host` inside the caller's ``collect`` span, which
    is told what came back (``bytes``) and the seconds of the fetch alone
    (``d2h_seconds``): the rest of a ``collect`` is the host's own
    unstacking. A span nobody records is told nothing, and nothing is
    measured for it."""
    if not span.recording:
        return fetch_to_host(tree)
    began = time.perf_counter()
    host = fetch_to_host(tree)
    span.set(
        d2h_seconds=round(time.perf_counter() - began, 6), bytes=tree_nbytes(host)
    )
    return host


def _bucket_nbytes(bucket) -> int:
    """Raw staged bytes of a bucket's members (span attribution)."""
    total = 0
    for member in bucket:
        if isinstance(member, WindowedFleetMember):
            total += member.series.nbytes + member.targets.nbytes
        else:
            total += member.X.nbytes
            if member.y is not member.X:
                total += member.y.nbytes
    return total


def _calibration_attrs(
    spec: ModelSpec, config: FitConfig, stacked_members: int, stacked_samples: int
):
    """The cost model's static features on a ``device_program`` span —
    exactly what :func:`gordo_tpu.planner.costmodel.calibrate` reads back
    from ``build_trace.jsonl`` to fit per-program correction factors."""
    return dict(
        params=spec_param_count(spec),
        flops_per_sample=spec_flops_per_sample(spec),
        stacked_members=int(stacked_members),
        stacked_samples=int(stacked_samples),
        epochs=config.epochs,
    )


def _fit_counter_attrs(spec: ModelSpec, counters, members: int) -> Dict[str, Any]:
    """A fit program's own counters (``spec.forward_aux_fn``: the router
    counts of an expert layer and the steps that ran, ``[members,
    epochs, ...]``) as span attributes: summed over the live members and
    the fit's epochs, said by the spec (``ModelSpec.fit_counter_attrs``),
    and named in ``fit_counters``, by which the builder copies them into
    ``build_status.json``. Read by the chip benchmark's backbone readers."""
    counters = fetch_to_host(counters)  # inside the caller's program span

    def total(value):
        # counts add up in int64; a counter that is a float32 (a sum of
        # losses, a count past int32) stays a float
        value = np.asarray(value)[:members]
        wide = np.int64 if np.issubdtype(value.dtype, np.integer) else np.float64
        return value.astype(wide).sum(axis=(0, 1))

    attrs = spec.fit_counter_attrs({name: total(value) for name, value in counters.items()})
    return {**attrs, "fit_counters": sorted(attrs)}


def _traced_outputs(outputs):
    """Block on a device program's outputs when a telemetry recorder is
    active, so the enclosing program span times real device work — jit
    dispatch is async and would otherwise measure ~0 for cache hits. The
    fetch right after waits on the same buffers, so the extra sync is
    free; with telemetry off this is a pass-through."""
    if telemetry.get_recorder().enabled:
        return jax.block_until_ready(outputs)
    return outputs


def _fill_weight_row(wtr, wval, i, n, member, config: FitConfig):
    """One member's train/val masks: explicit weights, or the Keras-style
    tail validation split over its ``n`` (virtual) samples."""
    if member.train_weights is not None:
        wtr[i, : len(member.train_weights)] = member.train_weights
    else:
        n_val = int(n * config.validation_split)
        wtr[i, : n - n_val] = 1.0
        if n_val:
            wval[i, n - n_val : n] = 1.0
    if member.val_weights is not None:
        wval[i, : len(member.val_weights)] = member.val_weights


def _jit_named(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` under an explicit name: the XLA module is
    ``jit_<name>`` in a profiler trace, whatever the wrapped function
    is called (docs/observability.md lists the names; the chip
    benchmark finds the fit programs by ``fit`` in theirs)."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


#: jit'd ravel+concat of same-dtype leaves: turns a many-leaf pytree fetch
#: into one contiguous device buffer, so the host sees ONE transfer.
_flat_concat = _jit_named(
    "fleet_flat_concat",
    lambda *leaves: jnp.concatenate([l.ravel() for l in leaves]),
)

#: _flat_concat compiles one XLA program per distinct (leaf count, shapes,
#: dtypes) signature for the process lifetime; trees with more leaves than
#: this are coalesced in chunks of this size rather than per-leaf — the
#: largest fleets are exactly where per-leaf round trips add up, while
#: chunking keeps each program's signature bounded so the jit cache
#: can't grow without limit.
_FLAT_CONCAT_MAX_LEAVES = 256

#: leaves of at least this many bytes are fetched on their own: above it
#: the fixed latency a coalesced fetch saves is noise beside the copy
#: (the LSTM cells' largest stacked leaf is 50 MB and stays coalesced; a
#: backbone's expert weights are 117 MB a leaf)
_COALESCE_MAX_LEAF_BYTES = 64 << 20

#: a fit whose ONE member holds a leaf of at least this many bytes does
#: not wait for its parameters (:func:`defers`): the line above, drawn
#: on a member's part of a stacked leaf. A backbone's expert weights are
#: 64-120 MB a leaf and the member is the bucket; the four-chip LSTM
#: cell's 64 members stack to leaves of exactly 64 MiB, 1 MiB a member,
#: and stay eager
DEFER_MIN_MEMBER_LEAF_BYTES = _COALESCE_MAX_LEAF_BYTES


def _fetched_alone(leaf) -> bool:
    """Whether ``fetch_to_host`` fetches ``leaf`` on its own and hands
    out the runtime's read-only array (the rest it coalesces, and hands
    out copies): one rule for both schedules, because a pickle tells
    the two kinds of array apart."""
    return leaf.nbytes >= _COALESCE_MAX_LEAF_BYTES


def defers(params) -> bool:
    """Whether a fit's stacked ``params`` are a large artifact, whose
    transfer is started and not waited for (models/in_flight.py): a
    member of it has a leaf that ``fetch_to_host`` would fetch on its
    own anyway. Everything else comes back at once, coalesced, and so
    does everything where the processes are several (the all-gather is
    a collective: every process waits in it together)."""
    if jax.process_count() > 1:
        return False
    return any(
        leaf.nbytes // leaf.shape[0] >= DEFER_MIN_MEMBER_LEAF_BYTES
        for leaf in jax.tree_util.tree_leaves(params)
    )


def _start_flight(params):
    """Start every leaf of ``params`` on its way, in the order of the
    tree, which is the order a pickler reaches them (a ``dict``'s keys
    sorted: what ``tree_map`` makes of one); returns the flight and the
    tree of its transfers. A leaf the eager schedule coalesces is a
    copy of its own there, and lands writable here."""
    flight = Flight()
    return flight, jax.tree_util.tree_map(
        lambda leaf: flight.start(leaf, writable=not _fetched_alone(leaf)), params
    )


def land_flights(flights: Iterable[Optional[Flight]]) -> None:
    """Wait for the parameters that earlier fits left on their way, so
    that no fit's block is on the device when the caller's next program
    starts there (``Flight.land``): a ``collect`` part of the seconds
    waited, which are the rest of those fits' fetch, and which starts
    nothing itself. Nothing on the eager schedule, and nothing the
    second time."""
    aloft = {flight for flight in flights if flight is not None and flight.aloft}
    if aloft:
        with telemetry.part_span("collect", bytes_deferred=0):
            for flight in aloft:
                flight.land()


def fetch_to_host(tree):
    """
    Device arrays → host numpy, multi-host safe: results of the sharded
    fleet programs span every process's devices, and ``device_get`` cannot
    fetch non-addressable shards — each process instead all-gathers the
    global value (one collective over ICI/DCN, symmetric across the SPMD
    processes). Single-process runs keep the plain ``device_get`` path.

    Single-process fetches of multi-leaf pytrees are COALESCED: every
    same-dtype leaf is raveled and concatenated on-device (one fused XLA
    program), fetched as one contiguous buffer, and sliced back on the
    host. Device→host readback pays a fixed per-transfer latency, so
    fetching a fleet's params/losses/epoch-counters as 11+ separate
    arrays costs 11 round trips where one or two suffice (the share of
    training wall-clock this saves is not measured on a directly
    attached chip).
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        # tiled=True is the only mode for global arrays (and for them it
        # just means "replicate the global value", no reshaping).
        return multihost_utils.process_allgather(tree, tiled=True)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(leaves) <= 1 or not all(isinstance(l, jax.Array) for l in leaves):
        return jax.device_get(tree)
    by_dtype: Dict[Any, List[int]] = {}
    host_leaves: List[Any] = [None] * len(leaves)
    for idx, leaf in enumerate(leaves):
        if _fetched_alone(leaf):
            # a large leaf's transfer dwarfs the round trip it would
            # save, and coalescing would copy it twice more (into the
            # concatenation on the device, out of it on the host)
            host_leaves[idx] = np.asarray(leaf)
        else:
            by_dtype.setdefault(leaf.dtype, []).append(idx)
    for idxs in by_dtype.values():
        for start in range(0, len(idxs), _FLAT_CONCAT_MAX_LEAVES):
            chunk = idxs[start : start + _FLAT_CONCAT_MAX_LEAVES]
            group = [leaves[i] for i in chunk]
            flat = np.asarray(_flat_concat(*group))
            offset = 0
            for i, leaf in zip(chunk, group):
                size = leaf.size
                # copy: a view would pin the whole coalesced buffer for as
                # long as any one leaf lives (e.g. one member's params kept
                # in a FleetResult would retain every pack's)
                host_leaves[i] = (
                    flat[offset : offset + size].reshape(leaf.shape).copy()
                )
                offset += size
    return jax.tree_util.tree_unflatten(treedef, host_leaves)


def host_prng_keys(seeds: Sequence[int]) -> np.ndarray:
    """
    Threefry PRNG keys built host-side, bit-identical to
    ``jax.random.PRNGKey(seed)`` (the uint32 pair ``(seed >> 32, seed &
    0xFFFFFFFF)`` in two's complement). ``PRNGKey`` is a tiny device
    program per call, one dispatch and round trip per member at fleet
    scale (cost not measured on a directly attached chip); building the
    keys on the host stages them with the rest of the bucket in one
    transfer. tests/parallel/test_fleet.py asserts the bit-equality.
    """
    if jax.config.jax_enable_x64:
        # int64 two's complement for negative seeds, like PRNGKey.
        raw = np.asarray(seeds, np.int64).view(np.uint64)
        hi = (raw >> np.uint64(32)).astype(np.uint32)
        lo = (raw & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    else:
        # x64 disabled (the default): PRNGKey casts the seed to int32, so
        # the high word is always zero and the low word wraps modulo 2^32.
        lo = np.asarray(seeds, np.int64).astype(np.int32).view(np.uint32)
        hi = np.zeros_like(lo)
    return np.stack([hi, lo], axis=-1)


def _over_members(spec: ModelSpec, fn):
    """``fn`` over a leading member axis: ``jax.vmap(fn)``, or, for a
    spec without a member axis (``ModelSpec.member_axis``), ``fn`` on
    the one member of a one-member bucket with the axis put back (two
    reshapes, so donated state still updates in place)."""
    if spec.member_axis:
        return jax.vmap(fn)

    def one_member(*args):
        members = jax.tree_util.tree_leaves(args)[0].shape[0]
        if members != 1:
            raise ValueError(
                f"{type(spec).__name__} trains and scores one member a "
                f"program (planner.packing.trains_alone); got {members}"
            )
        out = fn(*jax.tree_util.tree_map(lambda a: a[0], args))
        return jax.tree_util.tree_map(lambda a: a[None], out)

    return one_member


def _scaled(x, scaler):
    """``x[T, N]`` through a scaler's ``[4, T]`` parameters
    (:class:`FoldScoring`): sklearn's own steps in its order, so a
    ``MinMaxScaler``'s ``x * scale + min`` comes out bit for bit."""
    shift, mul, div, add = (scaler[k][:, None] for k in range(4))
    return (x - shift) * mul / div + add


def _explained_share(numerator, denominator):
    """``1 - numerator / denominator`` under sklearn's rule for a zero
    (``_assemble_fraction_of_explained_deviance``, ``force_finite``): a
    numerator of zero scores 1, else a denominator of zero scores 0."""
    defined = (numerator != 0) & (denominator != 0)
    share = 1.0 - numerator / jnp.where(defined, denominator, 1.0)
    return jnp.where(defined, share, jnp.where(numerator != 0, 0.0, 1.0))


def _over_run(values, run: int, combine, fill):
    """``combine`` over every ``values[..., t : t + run]`` by doubling;
    what lies past the end reads ``fill``."""
    covered = 1
    while covered < run:
        step = min(covered, run - covered)
        tail = jnp.full(values.shape[:-1] + (step,), fill, values.dtype)
        shifted = jnp.concatenate([values[..., step:], tail], axis=-1)
        values = combine(values, shifted)
        covered += step
    return values


def _run_min_max(values, usable, run: int):
    """``pd.rolling(run).min().max()`` along the last axis of
    ``values[K, N]``: the maximum over the complete runs of ``run`` usable
    values of the run's minimum. A run that holds a value that is not
    usable (a NaN, a row of padding) is skipped; no complete run, NaN."""
    if run > values.shape[-1]:
        return jnp.full(values.shape[:-1], jnp.nan, values.dtype)
    lows = _over_run(jnp.where(usable, values, jnp.inf), run, jnp.minimum, jnp.inf)
    spoiled = _over_run(~usable, run, jnp.logical_or, True)
    highest = jnp.max(jnp.where(spoiled, -jnp.inf, lows), axis=-1)
    return jnp.where(jnp.all(spoiled, axis=-1), jnp.nan, highest)


def fold_scores(y_true, y_pred, rows, metric_scaler, error_scaler, window=None):
    """
    One fold model's evaluation where its predictions are: what
    ``FleetBuilder._accumulate_metric_scores`` and
    ``_accumulate_thresholds`` reckon on the host from ``y_true[:rows]``
    and ``y_pred[:rows]`` (``[N, T]`` blocks; rows past ``rows`` are
    padding), in the same float32 arithmetic.

    - the four default metrics a tag (``[T]`` under sklearn's function
      names, its rule for a zero denominator and for R2 of one row
      included) of targets and predictions through ``metric_scaler``;
    - ``aggregate_threshold`` and ``feature_thresholds[T]``: of the mean
      over tags of the squared difference under ``error_scaler``, and of
      ``|y_true - y_pred|`` a tag, the maximum over time of the minimum
      over every complete run of ``threshold_run(rows)`` rows; with a
      ``window``, the same over that window under ``smooth_*``;
    - ``unscorable``: 1.0 where sklearn's metrics would refuse the fold
      (no row, or a value that is not finite), else 0.0.
    """
    truth, predicted = y_true.T, y_pred.T  # [T, N]: the rows along the lanes
    live = jnp.arange(truth.shape[-1]) < rows
    count = jnp.maximum(rows, 1).astype(truth.dtype)

    def total(values):
        return jnp.sum(jnp.where(live, values, 0.0), axis=-1)

    def mean(values):
        return total(values) / count

    truth_s, predicted_s = _scaled(truth, metric_scaler), _scaled(predicted, metric_scaler)
    diff = truth_s - predicted_s
    centred = truth_s - mean(truth_s)[:, None]
    residual, spread = total(diff * diff), total(centred * centred)
    unexplained = mean(jnp.square(diff - mean(diff)[:, None]))
    finite = jnp.isfinite(truth_s) & jnp.isfinite(predicted_s)
    scores = {
        "explained_variance_score": _explained_share(unexplained, spread / count),
        "r2_score": jnp.where(rows < 2, jnp.nan, _explained_share(residual, spread)),
        "mean_squared_error": residual / count,
        "mean_absolute_error": mean(jnp.abs(diff)),
        "unscorable": ((rows < 1) | jnp.any(live & ~finite)).astype(truth.dtype),
    }

    error = _scaled(predicted, error_scaler) - _scaled(truth, error_scaler)
    errors = jnp.concatenate(  # [T + 1, N]: a tag's absolute error, then the scaled MSE
        [jnp.abs(truth - predicted), jnp.mean(error * error, axis=0)[None]]
    )
    usable = live & ~jnp.isnan(errors)
    # a fold of fewer rows than a run takes all it has as its one run
    # (models/anomaly/diff.threshold_run)
    one_run = jnp.where(
        jnp.all(usable | ~live, axis=-1),
        jnp.min(jnp.where(live, errors, jnp.inf), axis=-1),
        jnp.nan,
    )
    thresholds = jnp.where(
        rows < THRESHOLD_RUN, one_run, _run_min_max(errors, usable, THRESHOLD_RUN)
    )
    scores["feature_thresholds"], scores["aggregate_threshold"] = (
        thresholds[:-1], thresholds[-1],
    )
    if window is not None:
        smooth = _run_min_max(errors, usable, window)
        scores["smooth_feature_thresholds"], scores["smooth_aggregate_threshold"] = (
            smooth[:-1], smooth[-1],
        )
    return scores


@lru_cache(maxsize=None)
def _fleet_fit_program(spec: ModelSpec, config: FitConfig):
    """jit(vmap) of the raw fused fit over a leading model axis."""
    raw_fit = build_raw_fit_fn(spec, config)
    return _jit_named("fleet_fit", jax.vmap(raw_fit))


@lru_cache(maxsize=None)
def _fleet_windowed_fit_program(spec: ModelSpec, config: FitConfig):
    """jit(vmap) of the on-device-windowing fused fit over the model axis."""
    from ..models.training import build_raw_windowed_fit_fn

    raw_fit = build_raw_windowed_fit_fn(spec, config)
    # params and optimizer state are donated: the trainer makes them for
    # this call alone, and a member whose state is half the chip cannot
    # live there twice (the inputs beside the loop's own carry)
    return _jit_named(
        "fleet_windowed_fit", _over_members(spec, raw_fit), donate_argnums=(0, 1)
    )


def _windowed_predict_fn(spec: ModelSpec, batch_size: int):
    """One windowed member's forward, its windows gathered from the raw
    series a scan step: ``(params, series[n, F], order[nv]) -> [nv, F_out]``."""
    forward = forward_fn_for(spec)
    lookback = spec.lookback_window

    def predict_one(params, series, order):
        steps = order.shape[0] // batch_size

        def step(_, starts):
            idx = starts[:, None] + jnp.arange(lookback)[None, :]
            out, _ = forward(spec, params, series[idx])
            return None, out

        _, outs = jax.lax.scan(
            step, None, order.reshape(steps, batch_size)
        )
        return outs.reshape(steps * batch_size, -1)

    return predict_one


def _predict_fn(spec: ModelSpec):
    """One dense member's forward: ``(params, X[N, ...]) -> [N, out]``."""
    forward = forward_fn_for(spec)

    def predict(params, X):
        return forward(spec, params, X)[0]

    return predict


def _scored(predict_one, window: Optional[int]):
    """``predict_one`` with :func:`fold_scores` of its predictions: the
    model's inputs, then a member's slice of a :class:`FoldScoring`."""

    def predict_and_score(*args):
        *inputs, y_true, rows, metric_scaler, error_scaler = args
        predictions = predict_one(*inputs)
        return predictions, fold_scores(
            y_true, predictions, rows, metric_scaler, error_scaler, window
        )

    return predict_and_score


@lru_cache(maxsize=None)
def fleet_windowed_predict_program(spec: ModelSpec, batch_size: int):
    """
    jit(vmap) forward for windowed members: windows gathered from the raw
    series per scan step, so prediction memory stays bounded like training.

    ``(stacked params, series[M, n, F], order[M, nv]) -> [M, nv, F_out]``
    (``nv`` must be a multiple of ``batch_size``).
    """
    return _jit_named(
        "fleet_windowed_predict",
        _over_members(spec, _windowed_predict_fn(spec, batch_size)),
    )


@lru_cache(maxsize=None)
def fleet_predict_program(spec: ModelSpec):
    """jit(vmap) forward: (stacked params, X[M, N, ...]) -> [M, N, out]."""
    return _jit_named("fleet_predict", jax.vmap(_predict_fn(spec)))


@lru_cache(maxsize=None)
def _fleet_windowed_predict_score_program(
    spec: ModelSpec, batch_size: int, window: Optional[int]
):
    """:func:`fleet_windowed_predict_program` and the fold scores of what
    it predicts in one program: ``(..., y_true[M, nv, F_out], rows[M],
    metric_scaler[M, 4, F_out], error_scaler[M, 4, F_out]) ->
    (predictions, scores)``."""
    return _jit_named(
        "fleet_windowed_predict_score",
        _over_members(spec, _scored(_windowed_predict_fn(spec, batch_size), window)),
    )


@lru_cache(maxsize=None)
def _fleet_predict_score_program(spec: ModelSpec, window: Optional[int]):
    """:func:`fleet_predict_program` and the fold scores of what it
    predicts in one program."""
    return _jit_named(
        "fleet_predict_score", jax.vmap(_scored(_predict_fn(spec), window))
    )


@lru_cache(maxsize=None)
def _fleet_init_program(spec: ModelSpec):
    init = init_fn_for(spec)

    def init_one(key):
        return init(key, spec)

    return _jit_named("fleet_init", _over_members(spec, init_one))


def _optimizer_init_program(spec: ModelSpec):
    """The stacked optimizer state's program (made anew per bucket: it
    traces and loads from the cache each time, never compiles twice)."""
    return _jit_named(
        "fleet_optimizer_init", jax.vmap(spec.optimizer.to_optax().init)
    )


def _block_params(stacked_params, m: int, m_total: int):
    """``stacked_params`` for a predict program's block of ``m_total``
    members: parameters that come ``m_total`` long are the block already
    (a fit's, on the device: :meth:`FleetTrainer.device_params`) and
    pass as they are; ``m`` members' are padded on the host by repeating
    the first."""
    if jax.tree_util.tree_leaves(stacked_params)[0].shape[0] == m_total:
        return stacked_params
    return jax.tree_util.tree_map(
        lambda a: np.concatenate(
            [a, np.repeat(np.asarray(a)[:1], m_total - m, axis=0)]
        ),
        stacked_params,
    )


def _resident_members(stacked_params, m: int) -> int:
    """Of a predict program's ``m`` members, those whose parameters it
    takes from the device: all where no leaf is a host array, else none
    (the ``params_resident_members`` of its span)."""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    return m if all(isinstance(leaf, jax.Array) for leaf in leaves) else 0


class FleetTrainer:
    """
    Trains homogeneous-spec buckets of models as single device programs.

    Parameters
    ----------
    mesh
        Fleet mesh (default: all local devices on the model axis).
    plan_strategy
        Bucket-construction strategy (``gordo_tpu.planner``): ``naive``
        (the historical exact-key grouping; the default, also via
        ``GORDO_TPU_PLAN_STRATEGY``) or ``packed`` (cost-model bin
        packing: geometric shape ladders, HBM caps, compile budget).
    fleet_plan
        An optional :class:`gordo_tpu.planner.FleetPlan`: members the
        plan covers train in their planned buckets with their planned
        pad targets; uncovered members (CV folds) pack live with
        ``plan_strategy``.
    cost_table
        A calibrated :class:`gordo_tpu.planner.CostTable` for the packed
        strategy's cost model (default: the analytic table).
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        plan_strategy: Optional[str] = None,
        fleet_plan: Optional[Any] = None,
        cost_table: Optional[Any] = None,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.plan_strategy = plan_strategy
        self.fleet_plan = fleet_plan
        self.cost_table = cost_table
        #: lifetime count of device-error bucket bisection events (the
        #: FleetBuilder folds the per-build delta into its robustness
        #: counters / Prometheus export)
        self.bucket_bisects = 0
        #: lifetime per-member split-event counts (member name -> events
        #: its bucket rode through); lets the builder attribute trainer-
        #: internal bisections to machines in BuildMetadata.robustness
        self.bisect_counts: Dict[str, int] = {}

    def _mesh_for(self, spec: ModelSpec) -> Mesh:
        """The mesh a bucket of ``spec`` runs on: the trainer's, or, for
        a spec without a member axis (``ModelSpec.member_axis``), the
        trainer's first device alone: one member has nothing to spread
        over ``models``, and no dummy member pads its bucket."""
        if spec.member_axis or self.mesh.devices.size == 1:
            return self.mesh
        return Mesh(
            self.mesh.devices.reshape(-1)[:1].reshape(1, 1), self.mesh.axis_names
        )

    # -- bucketing ----------------------------------------------------------
    # Bucket construction lives in gordo_tpu.planner.packing
    # (plan_train_buckets); the ``naive`` strategy there reproduces the
    # grouping that used to be FleetTrainer.bucket/bucket_windowed.

    def cost_model(self) -> CostModel:
        """The planner cost model bound to this trainer's mesh shape."""
        shape = self.mesh.devices.shape
        mesh_shape = (shape[0], shape[1] if len(shape) > 1 else 1)
        return CostModel(self.cost_table, mesh_shape=mesh_shape)

    def train(
        self,
        members: Sequence[Any],
        config: FitConfig,
        initial_params: Optional[Any] = None,
        retry_failed: int = 1,
        params_on_device: bool = False,
    ) -> List[FleetResult]:
        """
        Train all members (auto-bucketed); returns one FleetResult per
        member in input order. Accepts a mix of dense ``FleetMember``s and
        ``WindowedFleetMember``s (LSTM series with on-device windowing).

        ``retry_failed``: members whose training diverged (non-finite final
        loss) are re-vmapped into a retry bucket with a reseeded RNG, up to
        this many times — the chip-level analog of the reference DAG's
        per-pod retryStrategy (SURVEY.md §2.9 elasticity row).

        ``params_on_device``: the fits' parameters stay where the fit
        programs left them and only the histories come to the host: a
        result then has ``params`` None and refers to its bucket's
        ``block`` and its ``row`` in it (:meth:`device_params` stacks
        such results for a predict program). For models that exist to
        predict and go, a CV fold's; a fit whose parameters are the
        artifact takes them to the host (the default), and where they
        are a large one (:func:`defers`) does not wait for them: the
        result's ``params`` then hold leaves on their way
        (``FleetResult.in_flight``, models/in_flight.py), which
        ``in_flight.landed`` turns into the ``numpy`` arrays they become.

        CONTRACT: a member whose device program fails in ISOLATION (after
        bucket bisection of a ``JaxRuntimeError``/``RESOURCE_EXHAUSTED``)
        does NOT raise — it returns a ``FleetResult`` with ``params=None``
        and the exception in ``error``. Callers must check
        ``result.error`` before using ``result.params`` (FleetBuilder
        degrades such machines to the sequential builder). Host-side
        exceptions still raise for the whole call, as before.
        """
        results = self._train_once(members, config, params_on_device)
        for attempt in range(1, retry_failed + 1):
            failed_idx = [
                i
                for i, r in enumerate(results)
                if r.history.history["loss"]
                and not np.isfinite(r.history.history["loss"][-1])
            ]
            if not failed_idx:
                break
            logger.warning(
                "Fleet retry %d: %d member(s) diverged (%s); reseeding",
                attempt,
                len(failed_idx),
                ", ".join(results[i].name for i in failed_idx[:5]),
            )
            retry_members = []
            for i in failed_idx:
                member = replace(
                    members[i], seed=members[i].seed + 7919 * attempt
                )
                retry_members.append(member)
            land_flights(result.in_flight for result in results)  # as before every fit
            retried = self._train_once(retry_members, config, params_on_device)
            for i, result in zip(failed_idx, retried):
                result.retries = attempt
                result.history.params["fleet_retry"] = {
                    "retries": attempt,
                    "seed": result.seed,
                }
                results[i] = result
        return results

    def _train_once(
        self, members: Sequence[Any], config: FitConfig, params_on_device: bool = False
    ) -> List[FleetResult]:
        by_name: Dict[str, FleetResult] = {}
        failures: Dict[str, BaseException] = {}
        planned = plan_train_buckets(
            members,
            config,
            strategy=self.plan_strategy,
            cost_model=self.cost_model(),
            plan=self.fleet_plan,
        )
        def bucket_m_padded(pb, b):
            """The planned member-axis floor — only while the bucket is
            intact. A bisected half (the OOM recovery ladder) must NOT
            pad back up to the planned rung, or every half re-OOMs at
            the original shape and bisection can never converge."""
            return pb.m_padded if len(b) == len(pb.members) else None

        for pb in planned:
            bucket = pb.members
            if pb.windowed:
                logger.info(
                    "Windowed fleet bucket %s: %d models, spec=%s, padded_n=%d",
                    pb.bucket_id,
                    len(bucket),
                    type(pb.spec).__name__,
                    pb.n_padded,
                )
                self._run_bucket_degraded(
                    lambda b, _p=pb: self._train_windowed_bucket(
                        _p.spec, _p.n_padded, _p.offset, b, config,
                        m_padded=bucket_m_padded(_p, b),
                        params_on_device=params_on_device,
                    ),
                    bucket,
                    by_name,
                    failures,
                )
                continue
            logger.info(
                "Fleet bucket %s: %d models, spec=%s, padded_n=%d",
                pb.bucket_id,
                len(bucket),
                type(pb.spec).__name__,
                pb.n_padded,
            )
            self._run_bucket_degraded(
                lambda b, _p=pb: self._train_bucket(
                    _p.spec, _p.n_padded, b, config,
                    m_padded=bucket_m_padded(_p, b),
                    params_on_device=params_on_device,
                ),
                bucket,
                by_name,
                failures,
            )
        for member in members:
            if member.name in failures:
                by_name[member.name] = FleetResult(
                    name=member.name,
                    params=None,
                    history=History(history={"loss": []}, params={}, epoch=[]),
                    seed=member.seed,
                    error=failures[member.name],
                )
        return [by_name[m.name] for m in members]

    def _run_bucket_degraded(self, run, bucket, by_name, failures) -> None:
        """
        Run one bucket's device program with degradation: an
        ``JaxRuntimeError``/``RESOURCE_EXHAUSTED`` failure bisects the
        bucket and retries each half recursively — an over-packed bucket
        resolves by splitting, a poisonous member is isolated down to a
        single-member program whose failure lands in ``failures`` (the
        member's FleetResult carries it as ``error``) instead of taking
        the whole fleet down. Host-side exceptions propagate unchanged:
        they are deterministic and would fail every half identically.
        """
        try:
            for member in bucket:
                fault_point("device_program", member.name)
            # an earlier bucket's parameters that are still on their way
            # are still on the chip: this fit does not run beside them
            land_flights(result.in_flight for result in by_name.values())
            results = run(bucket)
        except Exception as exc:
            if not is_device_error(exc):
                raise
            if len(bucket) == 1:
                logger.error(
                    "Device program failed for member %s in isolation: %r",
                    bucket[0].name,
                    exc,
                )
                telemetry.get_recorder().event(
                    "member_isolated", member=bucket[0].name, error=repr(exc)
                )
                failures[bucket[0].name] = exc
                return
            mid = len(bucket) // 2
            self.bucket_bisects += 1
            telemetry.get_recorder().event(
                "bucket_bisect", members=len(bucket), error=repr(exc)
            )
            for member in bucket:
                self.bisect_counts[member.name] = (
                    self.bisect_counts.get(member.name, 0) + 1
                )
            logger.warning(
                "Device program failed for bucket of %d members (%s); "
                "bisecting into %d + %d",
                len(bucket),
                exc,
                mid,
                len(bucket) - mid,
            )
            self._run_bucket_degraded(run, bucket[:mid], by_name, failures)
            self._run_bucket_degraded(run, bucket[mid:], by_name, failures)
            return
        for result in results:
            by_name[result.name] = result

    def _stack_bucket(
        self,
        spec: ModelSpec,
        n_padded: int,
        bucket: List[FleetMember],
        config: FitConfig,
        blocks: host_blocks.Lease,
        m_padded: Optional[int] = None,
    ):
        """Stack + mask a bucket; returns the device-sharded ``(X, y, wtr,
        Xval, yval, wval, rngs)`` and the bucket's ``validation_slots``. With no validation row in the
        bucket the validation arrays have no rows
        (models.training.validation_inputs) and the program no
        validation pass; else they are ``X`` and ``y`` again, masked.
        ``y`` and ``yval`` are None where every member's target is its
        input (models.training.build_raw_fit_fn).

        The model axis is padded with zero-weight dummies up to a multiple
        of the mesh's model-axis size (sharding requires divisibility);
        dummy results are dropped by the caller. The sample axis is padded
        to a multiple of the data-axis size for the same reason.
        ``m_padded`` raises the member-axis floor further (the packed
        planner pads sibling HBM-split buckets to one shared rung so they
        reuse a single compiled program).

        The host blocks are ``blocks``' (parallel/host_blocks.py): the
        caller ends that lease when the program that reads the returned
        arrays has answered, and keeps none of them past it.
        """
        with telemetry.part_span("stack") as span:
            model_axis = self.mesh.devices.shape[0]
            data_axis = self.mesh.devices.shape[1] if self.mesh.devices.ndim > 1 else 1
            m_floor = max(len(bucket), m_padded or 0)
            m_total = -(-m_floor // model_axis) * model_axis
            # The sample axis must stay a whole number of batches (the fit
            # program reshapes [steps, batch]) AND divide across the data axis.
            step = int(np.lcm(config.batch_size, data_axis))
            n_padded = -(-n_padded // step) * step

            def stacked(attr_arrays):
                # Fill one block instead of pad-then-np.stack: one copy per
                # member, zero rows double as sample padding and
                # zero-weight dummy models.
                return blocks.stacked(
                    (m_total, n_padded) + np.shape(attr_arrays[0])[1:], attr_arrays
                )

            X = stacked([m.X for m in bucket])
            # A bare AE trains y == X: the block is staged once and the
            # program told so (no target array), which saves a second
            # 100s-of-MB host copy, its transfer, and on the device a second
            # copy of every sample in the rows the steps gather.
            y = None if all(m.y is m.X for m in bucket) else stacked([m.y for m in bucket])

            wtr = blocks.zeros((m_total, n_padded))
            wval = blocks.zeros((m_total, n_padded))
            for i, member in enumerate(bucket):
                _fill_weight_row(wtr, wval, i, member.n, member, config)
            if span.recording:  # the blocks filled
                span.set(
                    bytes=tree_nbytes(X, y, wtr, wval),
                    bytes_reused=blocks.bytes_reused,
                )
            validation_slots, wval, Xval, yval = validation_inputs(
                wval, X, y, axis=1
            )

            rngs = host_prng_keys([m.seed for m in bucket] + [0] * (m_total - len(bucket)))
        with telemetry.part_span("h2d") as span:
            w_sharding = model_data_sharding(self.mesh)

            def put(a):
                if a is None:  # no target array
                    return None
                return jax.device_put(
                    a, model_data_sharding(self.mesh, extra_dims=a.ndim - 2)
                )

            X_dev, y_dev = put(X), put(y)
            Xval_dev, yval_dev = (
                (X_dev, y_dev) if validation_slots else (put(Xval), put(yval))
            )
            if span.recording:
                span.set(
                    bytes=tree_nbytes(X, y, wtr, wval, rngs)
                    + (0 if validation_slots else tree_nbytes(Xval, yval))
                )
            wtr, wval, rngs = jax.device_put(
                (wtr, wval, rngs),
                (w_sharding, w_sharding, model_sharding(self.mesh, extra_dims=1)),
            )
            return (X_dev, y_dev, wtr, Xval_dev, yval_dev, wval, rngs), validation_slots

    def _train_bucket(
        self,
        spec: ModelSpec,
        n_padded: int,
        bucket: List[FleetMember],
        config: FitConfig,
        m_padded: Optional[int] = None,
        params_on_device: bool = False,
    ) -> List[FleetResult]:
        # the lease ends where the fit's results are on the host: the
        # program has then read every array put from the blocks, and none
        # of them outlives this frame
        with host_blocks.lease() as blocks:
            (*data, rngs), validation_slots = self._stack_bucket(
                spec, n_padded, bucket, config, blocks, m_padded=m_padded
            )
            X, y, wval = data[0], data[1], data[-1]
            y_row = None if y is None else y.shape[2:]
            params, opt_state, rngs = self._init_bucket_params(spec, rngs)
            fit = _fleet_fit_program(spec, config)
            with telemetry.program_span(
                "fleet_fit",
                (spec, config, X.shape, y_row, wval.shape),
                members=len(bucket),
                shape=str(tuple(X.shape)),
                spec=type(spec).__name__,
                bytes=_bucket_nbytes(bucket),
                validation_slots=validation_slots,
                shuffle_columns=shuffle_columns(config, X.shape[2:], y_row),
                fit_counters=["shuffle_columns"],
                **_calibration_attrs(spec, config, X.shape[0], X.shape[1]),
            ):
                params, _, losses, val_losses, epochs_ran = _traced_outputs(
                    fit(params, opt_state, *data, rngs)
                )
            return self._collect_results(
                bucket, params, losses, val_losses, epochs_ran, config,
                steps=n_padded // config.batch_size,
                params_on_device=params_on_device,
            )

    def _init_bucket_params(self, spec: ModelSpec, rngs):
        """Per-member init mirroring fit_single's derivation exactly so a
        fleet member trains bit-for-bit like the single-model path: fit rng
        and init rng are the two halves of split(PRNGKey(seed))."""
        with telemetry.part_span("init"):
            split_keys = jax.vmap(jax.random.split)(rngs)
            rngs, init_rngs = split_keys[:, 0], split_keys[:, 1]
            params = _fleet_init_program(spec)(init_rngs)
            params = jax.device_put(
                params, model_sharding(self._mesh_for(spec), extra_dims=0)
            )
            opt_state = _optimizer_init_program(spec)(params)
            return params, opt_state, rngs

    # -- windowed training --------------------------------------------------

    def _stack_windowed_bucket(
        self,
        spec: ModelSpec,
        n_padded: int,
        offset: int,
        bucket: List[WindowedFleetMember],
        config: FitConfig,
        blocks: host_blocks.Lease,
        m_padded: Optional[int] = None,
    ):
        """Stack a windowed bucket; series replicated over the data axis.
        Returns the device-sharded ``(series, ytgt, order, wtr, wval,
        rngs)`` and the bucket's ``validation_slots``; with none, ``wval``
        has no slots (models.training.validation_inputs) and the program
        no validation pass.

        The per-batch window gather indexes arbitrary series rows, so the
        series (and aligned targets) shard over ``models`` only; the
        virtual window axis (order + weights) shards over ``data``.
        The host blocks are ``blocks``', as in :meth:`_stack_bucket`.
        """
        mesh = self._mesh_for(spec)
        with telemetry.part_span("stack") as span:
            model_axis = mesh.devices.shape[0]
            data_axis = mesh.devices.shape[1] if mesh.devices.ndim > 1 else 1
            m_floor = max(len(bucket), m_padded or 0)
            m_total = -(-m_floor // model_axis) * model_axis
            nw_padded = n_padded - offset
            step = int(np.lcm(config.batch_size, data_axis))
            nv_padded = -(-nw_padded // step) * step

            f_in = bucket[0].series.shape[1]
            f_out = bucket[0].targets.shape[1]
            series = blocks.stacked(
                (m_total, n_padded, f_in), (m.series for m in bucket)
            )
            ytgt = blocks.stacked(
                (m_total, nw_padded, f_out), (m.targets for m in bucket)
            )
            order = blocks.stacked(
                (m_total, nv_padded),
                (
                    m.order if m.order is not None else np.arange(m.n_windows)
                    for m in bucket
                ),
                np.int32,
            )
            wtr = blocks.zeros((m_total, nv_padded))
            wval = blocks.zeros((m_total, nv_padded))
            for i, member in enumerate(bucket):
                _fill_weight_row(wtr, wval, i, member.n_windows, member, config)
            if span.recording:
                span.set(
                    bytes=tree_nbytes(series, ytgt, order, wtr, wval),
                    bytes_reused=blocks.bytes_reused,
                )
            validation_slots, wval = validation_inputs(wval, axis=1)

            rngs = host_prng_keys(
                [m.seed for m in bucket] + [0] * (m_total - len(bucket))
            )
        with telemetry.part_span("h2d") as span:
            md = model_data_sharding(mesh)
            if span.recording:
                span.set(bytes=tree_nbytes(series, ytgt, order, wtr, wval, rngs))
            arrays = jax.device_put(
                (series, ytgt, order, wtr, wval, rngs),
                (
                    model_sharding(mesh, extra_dims=2),
                    model_sharding(mesh, extra_dims=2),
                    md,
                    md,
                    md,
                    model_sharding(mesh, extra_dims=1),
                ),
            )
            return arrays, validation_slots

    def _train_windowed_bucket(
        self,
        spec: ModelSpec,
        n_padded: int,
        offset: int,
        bucket: List[WindowedFleetMember],
        config: FitConfig,
        m_padded: Optional[int] = None,
        params_on_device: bool = False,
    ) -> List[FleetResult]:
        with host_blocks.lease() as blocks:  # as in _train_bucket
            (series, ytgt, order, wtr, wval, rngs), validation_slots = (
                self._stack_windowed_bucket(
                    spec, n_padded, offset, bucket, config, blocks, m_padded=m_padded
                )
            )
            params, opt_state, rngs = self._init_bucket_params(spec, rngs)
            fit = _fleet_windowed_fit_program(spec, config)
            with telemetry.program_span(
                "fleet_windowed_fit",
                (spec, config, series.shape, order.shape, wval.shape),
                tokens_per_step=config.batch_size * spec.lookback_window,
                members=len(bucket),
                shape=str(tuple(series.shape)),
                spec=type(spec).__name__,
                bytes=_bucket_nbytes(bucket),
                validation_slots=validation_slots,
                **_calibration_attrs(
                    spec, config, series.shape[0], order.shape[1]
                ),
            ) as span:
                params, _, losses, val_losses, epochs_ran, *counters = (
                    _traced_outputs(
                        fit(params, opt_state, series, ytgt, order, wtr, wval, rngs)
                    )
                )
                if counters:
                    span.set(**_fit_counter_attrs(spec, counters[0], len(bucket)))
            return self._collect_results(
                bucket, params, losses, val_losses, epochs_ran, config,
                steps=order.shape[1] // config.batch_size,
                params_on_device=params_on_device,
            )

    def _collect_results(
        self, bucket, params, losses, val_losses, epochs_ran, config, steps,
        params_on_device: bool = False,
    ) -> List[FleetResult]:
        """A fit program's results, a member each: the ``collect`` part,
        whose span says the bytes fetched and the fetch's own seconds
        (``_fetch_for``); the rest of it is the loop below. The
        histories come to the host, and so do the parameters unless
        ``params_on_device``: then they are not touched, and every
        result refers to ``params`` itself, the program's block, and to
        its row in it. The parameters of a large artifact (``defers``)
        are started on their way and not waited for: the span says how
        many bytes (``bytes_deferred``, 0 on the eager schedule), and
        every result holds leaves in flight."""
        with telemetry.part_span("collect") as span:
            deferred = not params_on_device and defers(params)
            host_params, losses, val_losses, epochs_ran = _fetch_for(
                span,
                (
                    None if params_on_device or deferred else params,
                    losses,
                    val_losses,
                    epochs_ran,
                ),
            )
            flight = None
            if deferred:
                # after the histories: a small fetch asked for behind
                # these would wait for every one of them
                flight, host_params = _start_flight(params)
            if span.recording and not params_on_device:
                span.set(bytes_deferred=flight.bytes_started if flight else 0)
            losses = np.asarray(losses)
            val_losses = np.asarray(val_losses)
            epochs_ran = np.asarray(epochs_ran)

            results = []
            for i, member in enumerate(bucket):
                ran = int(epochs_ran[i])
                history = {"loss": [float(l) for l in losses[i][:ran]]}
                member_val = val_losses[i][:ran]
                # NaN marks "no validation rows for this member" (see
                # weighted_mean_loss); only members with real validation
                # data get a val_loss history.
                if ran and not np.all(np.isnan(member_val)):
                    history["val_loss"] = [float(l) for l in member_val]
                member_params = jax.tree_util.tree_map(
                    (lambda a: LeafInFlight(a, i))
                    if deferred
                    else (lambda a: np.asarray(a[i])),
                    host_params,
                )
                results.append(
                    FleetResult(
                        name=member.name,
                        seed=member.seed,
                        params=member_params,
                        block=params if params_on_device else None,
                        row=i,
                        in_flight=flight,
                        history=History(
                            history=history,
                            params={
                                "epochs": config.epochs,
                                "steps": steps,
                                "verbose": 0,
                                "metrics": list(history),
                            },
                            epoch=list(range(ran)),
                        ),
                    )
                )
            return results

    # -- prediction ---------------------------------------------------------

    def device_params(self, spec: ModelSpec, results: Sequence[FleetResult]):
        """
        The stacked parameters of fits that left them on the device
        (``train(params_on_device=True)``), for :meth:`predict_bucket` /
        :meth:`predict_windowed_bucket`: ``results``' members in order,
        then padding up to the mesh's model axis, sharded over ``models``.

        Where ``results`` are one bucket's rows from its first on, and
        the bucket's block is as long as the predict program's (the usual
        case: a group of fold models is the bucket that trained them),
        that is the block itself and nothing moves. Otherwise (a group
        out of several buckets, a reseeded retry's, part of a bucket) the
        rows are gathered on the device. No leaf comes to the host.
        """
        mesh = self._mesh_for(spec)
        model_axis = mesh.devices.shape[0]
        m_total = -(-len(results) // model_axis) * model_axis
        first = results[0].block
        length = jax.tree_util.tree_leaves(first)[0].shape[0]
        if (
            length == m_total
            and all(r.block is first for r in results)
            and [r.row for r in results] == list(range(len(results)))
        ):
            return first
        # runs of rows out of one block each; the padding repeats the
        # first member, as the host's does (_block_params)
        padded = list(results) + [results[0]] * (m_total - len(results))
        runs: List[Any] = []
        for result in padded:
            if runs and runs[-1][0] is result.block:
                runs[-1][1].append(result.row)
            else:
                runs.append((result.block, [result.row]))
        rows = [np.asarray(run_rows, np.int32) for _, run_rows in runs]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(
                [jnp.take(leaf, r, axis=0) for leaf, r in zip(leaves, rows)]
            ),
            *[block for block, _ in runs],
        )
        return jax.device_put(stacked, model_sharding(mesh, extra_dims=0))

    def _put_scoring(
        self, mesh: Mesh, scoring: FoldScoring, m_total: int, n_total: int, rows_sharding
    ):
        """``scoring``'s arrays padded to the program's block (a member of
        padding counts no rows) and put on the mesh beside the inputs:
        ``y_true`` like the predictions it is held against."""
        y_true = scoring.y_true
        m, n = y_true.shape[:2]
        if (m, n) != (m_total, n_total):
            y_true = np.zeros((m_total, n_total, y_true.shape[2]), np.float32)
            y_true[:m, :n] = scoring.y_true
        rows = np.zeros(m_total, np.int32)
        rows[:m] = scoring.rows
        scalers = []
        for scaler in (scoring.metric_scaler, scoring.error_scaler):
            padded = np.ones((m_total,) + scaler.shape[1:], np.float32)
            padded[:m] = scaler
            scalers.append(padded)
        per_member = model_sharding(mesh, extra_dims=2)
        return jax.device_put(
            (y_true, rows, *scalers),
            (rows_sharding, model_sharding(mesh), per_member, per_member),
        )

    @staticmethod
    def _collect_predictions(out, scoring: Optional[FoldScoring], m: int, n: int):
        """What a predict program hands back: the predictions on the
        host, or, of a program that scored them (``scoring``), the
        predictions where they are and the scores of the ``m`` members
        on the host."""
        with telemetry.part_span("collect") as span:
            if scoring is None:
                return np.asarray(_fetch_for(span, out))[:m, :n]
            predictions, scores = out
            scores = _fetch_for(span, scores)
        return predictions, {k: np.asarray(v)[:m] for k, v in scores.items()}

    def predict_bucket(
        self,
        spec: ModelSpec,
        stacked_params,
        X: np.ndarray,
        scoring: Optional[FoldScoring] = None,
    ):
        """Forward the whole bucket: X[M, N, ...] -> [M, N, out].
        ``stacked_params``: the ``M`` members' on the host (padded here
        to the program's block), or the block itself on the device
        (:meth:`device_params`), which goes to the program as it is. With
        ``scoring``, the same program goes on to :func:`fold_scores`: the
        predictions stay on the device (``[M', N', out]``, the program's
        padded block) and come back beside the scores, ``[M, ...]`` arrays
        on the host."""
        with telemetry.part_span("h2d") as span:  # pad to the mesh, then transfer
            X = np.asarray(X, np.float32)
            m = X.shape[0]
            model_axis = self.mesh.devices.shape[0]
            data_axis = (
                self.mesh.devices.shape[1] if self.mesh.devices.ndim > 1 else 1
            )
            m_total = -(-m // model_axis) * model_axis
            n = X.shape[1]
            n_total = -(-n // data_axis) * data_axis
            if m_total != m or n_total != n:
                padded = np.zeros((m_total, n_total) + X.shape[2:], X.dtype)
                padded[:m, :n] = X
                X = padded
            stacked_params = _block_params(stacked_params, m, m_total)
            if span.recording:
                # the parameters go with the program's call, host arrays too
                span.set(
                    bytes=tree_nbytes(X, stacked_params, on_host=True)
                    + (scoring.nbytes if scoring is not None else 0)
                )
            X = jax.device_put(
                X, model_data_sharding(self.mesh, extra_dims=X.ndim - 2)
            )
            program, name, scored = fleet_predict_program(spec), "fleet_predict", ()
            if scoring is not None:
                program = _fleet_predict_score_program(spec, scoring.window)
                name = "fleet_predict_score"
                scored = self._put_scoring(
                    self.mesh, scoring, m_total, n_total,
                    model_data_sharding(self.mesh, extra_dims=1),
                )
        resident = _resident_members(stacked_params, m)
        with telemetry.program_span(
            name,
            (spec, X.shape, scoring and scoring.window, bool(resident)),
            members=m,
            params_resident_members=resident,
            shape=str(tuple(X.shape)),
            spec=type(spec).__name__,
            **spec.program_attrs(),
        ):
            out = _traced_outputs(program(stacked_params, X, *scored))
            return self._collect_predictions(out, scoring, m, n)

    def predict_windowed_bucket(
        self,
        spec: ModelSpec,
        stacked_params,
        series: np.ndarray,
        order: np.ndarray,
        batch_size: int = 256,
        scoring: Optional[FoldScoring] = None,
    ):
        """
        Forward a windowed bucket with on-device window gathering, sharded
        over the mesh's model axis like :meth:`predict_bucket`:
        ``series[M, n, F]`` + ``order[M, nv]`` → ``[M, nv, F_out]``
        (``nv`` is padded to a whole number of ``batch_size`` batches here).
        ``stacked_params`` and ``scoring`` as in :meth:`predict_bucket`.
        """
        mesh = self._mesh_for(spec)
        with telemetry.part_span("h2d") as span:  # pad to the mesh, then transfer
            series = np.asarray(series, np.float32)
            order = np.asarray(order, np.int32)
            m = series.shape[0]
            model_axis = mesh.devices.shape[0]
            m_total = -(-m // model_axis) * model_axis
            nv = order.shape[1]
            nv_pad = -(-nv // batch_size) * batch_size
            if m_total != m or nv_pad != nv:
                series = np.concatenate(
                    [series, np.repeat(series[:1], m_total - m, axis=0)]
                ) if m_total != m else series
                padded_order = np.zeros((m_total, nv_pad), np.int32)
                padded_order[:m, :nv] = order
                order = padded_order
            stacked_params = _block_params(stacked_params, m, m_total)
            ms2 = model_sharding(mesh, extra_dims=2)
            if span.recording:
                span.set(
                    bytes=tree_nbytes(series, order, stacked_params, on_host=True)
                    + (scoring.nbytes if scoring is not None else 0)
                )
            series = jax.device_put(series, ms2)
            order = jax.device_put(
                order, model_sharding(mesh, extra_dims=1)
            )
            program = fleet_windowed_predict_program(spec, batch_size)
            name, scored = "fleet_windowed_predict", ()
            if scoring is not None:
                program = _fleet_windowed_predict_score_program(
                    spec, batch_size, scoring.window
                )
                name = "fleet_windowed_predict_score"
                scored = self._put_scoring(mesh, scoring, m_total, nv_pad, ms2)
        resident = _resident_members(stacked_params, m)
        with telemetry.program_span(
            name,
            (
                spec, batch_size, series.shape, order.shape,
                scoring and scoring.window, bool(resident),
            ),
            members=m,
            params_resident_members=resident,
            shape=str(tuple(series.shape)),
            spec=type(spec).__name__,
            **spec.program_attrs(),
        ):
            out = _traced_outputs(program(stacked_params, series, order, *scored))
            return self._collect_predictions(out, scoring, m, nv)


def fetch_members(stacked, members: Sequence[int]) -> np.ndarray:
    """The ``members`` of a stacked device array on the host (the fold
    models whose predictions the host scores), and no other's."""
    return np.asarray(fetch_to_host(stacked[np.asarray(members, np.int32)]))


def stack_member_params(results: Sequence[FleetResult]):
    """Re-stack per-member host params into a fleet pytree (serving path).
    One member's leaves get their member axis as a view, not a copy."""
    if len(results) == 1:
        return jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf)[None], results[0].params
        )
    return jax.tree_util.tree_map(
        lambda *leaves: np.stack(leaves), *[r.params for r in results]
    )
