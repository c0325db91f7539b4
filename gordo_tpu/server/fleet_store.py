"""
Fleet-resident model store: the TPU-native replacement for the
reference's LRU(2)-of-pickles serving cache (gordo/server/utils.py:334-353).

The reference serves thousands of tiny models by unpickling whichever two
were requested most recently — every other request pays a full disk load
plus (here) a host→device parameter transfer. A TPU fleet's models are
small enough to keep *all* of them resident: this store keeps one
:class:`RevisionFleet` per served revision directory, each holding every
loaded model with its JAX parameters already on device, plus per-spec
**buckets** of stacked parameters (``parallel.fleet.stack_member_params``)
so whole-fleet scoring runs as one device program — through the Pallas
fused kernel (:func:`gordo_tpu.ops.pallas_dense.fleet_feedforward_pallas`)
on TPU, or the XLA vmapped forward elsewhere.

Consistency contract: a model is loaded at most once per revision
directory; the DELETE-revision route invalidates the store, and metadata
existence is still re-checked per request by the caller (the same
staleness rule the reference documents for its LRU caches).
"""

import logging
import os
import threading
from collections import OrderedDict
from functools import lru_cache
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import serializer
from ..models.estimators import JaxBaseEstimator
from ..models.spec import FeedForwardSpec
from ..planner.packing import trains_alone, windowed_scoring_batch
from ..utils.env import env_bool, env_int

logger = logging.getLogger(__name__)


def _find_estimator(model: Any) -> Optional[JaxBaseEstimator]:
    """The JAX estimator inside a served object graph (detector and/or
    sklearn Pipeline wrappers), or None for non-JAX models."""
    obj = model
    base = getattr(obj, "base_estimator", None)
    if base is not None:
        obj = base
    steps = getattr(obj, "steps", None)
    if steps:
        obj = steps[-1][1]
    return obj if isinstance(obj, JaxBaseEstimator) else None


def _host_transform(model: Any, X):
    """Apply any host-side pipeline transformers ahead of the estimator
    (scalers etc.); mirrors the pipeline's own predict path."""
    obj = model
    base = getattr(obj, "base_estimator", None)
    if base is not None:
        obj = base
    steps = getattr(obj, "steps", None)
    if steps:
        for _, transformer in steps[:-1]:
            X = transformer.transform(X)
    return np.asarray(X, np.float32)


class ModelLoadError(Exception):
    """
    A model artifact failed to LOAD (as opposed to failing to score a
    request). Routes must not echo the underlying cause — load errors are
    server-side and their text can carry filesystem paths; the cause is
    chained for the server log only.
    """


class ModelResolution:
    """
    Everything the serving routes derive from one model's artifacts, at
    most once per revision: the loaded model, parsed metadata/info, tag
    lists (both as :class:`SensorTag` and as plain names), the training
    frequency offset, the detector's threshold arrays, and the wire
    column-alignment plans. A CPU run of the route (PR 7) measured
    ``model_resolve`` at 50.9ms p50 (7.5% of the route) — almost all of
    it the per-request zlib+pickle metadata round-trip and tag
    re-normalization this object exists to not repeat: a request now
    pays dict probes.

    Pinned to the :class:`RevisionFleet` snapshot, so the DELETE/hot-swap
    invalidation contract is inherited wholesale (an invalidated revision
    drops its fleet object, resolutions and all); callers still re-check
    ``metadata.json`` existence per request, as with every other cache.
    """

    __slots__ = (
        "name",
        "model",
        "metadata",
        "info",
        "tags",
        "target_tags",
        "tag_names",
        "target_names",
        "feature_thresholds",
        "aggregate_threshold",
        "_frequency",
        "_plans",
    )

    def __init__(self, name: str, model: Any, metadata: dict, info: dict):
        from types import SimpleNamespace

        from .properties import get_frequency, get_tags, get_target_tags

        self.name = name
        self.model = model
        self.metadata = metadata
        self.info = info
        carrier = SimpleNamespace(metadata=metadata)
        self.tags = get_tags(carrier)
        self.target_tags = get_target_tags(carrier)
        self.tag_names = [t.name for t in self.tags]
        self.target_names = [t.name for t in self.target_tags]
        try:
            self._frequency = ("ok", get_frequency(carrier))
        except Exception as exc:  # noqa: BLE001 - re-raised per access
            self._frequency = ("error", exc)
        thresholds = getattr(model, "feature_thresholds_", None)
        self.feature_thresholds = (
            np.asarray(thresholds.values, dtype=float)
            if thresholds is not None
            else None
        )
        aggregate = getattr(model, "aggregate_threshold_", None)
        self.aggregate_threshold = (
            float(aggregate) if aggregate is not None else None
        )
        self._plans: Dict[Tuple, Tuple[str, ...]] = {}

    @property
    def frequency(self):
        """The training resolution as a pandas offset. Errors are cached
        too and re-raised per access — the route's error contract for a
        bad ``dataset.resolution`` must not depend on cache state."""
        kind, value = self._frequency
        if kind == "error":
            raise value
        return value

    def alignment(
        self, names: Tuple[str, ...], expected: Tuple[str, ...]
    ) -> Optional[Tuple[str, ...]]:
        """The cached column-selection plan for a client column set
        against ``expected`` tag order: the tuple of client column names
        to stack, or None when no plan is cached yet. Bounded: plans are
        keyed by client-supplied column tuples, so the dict is capped
        against adversarial churn."""
        return self._plans.get((names, expected))

    def remember_alignment(
        self,
        names: Tuple[str, ...],
        expected: Tuple[str, ...],
        order: Tuple[str, ...],
    ) -> None:
        if len(self._plans) >= 1024:
            self._plans.clear()
        self._plans[(names, expected)] = order


class RevisionFleet:
    """
    All models of one revision directory, loaded lazily but retained for
    the life of the revision (no per-request eviction thrash). Feedforward
    and LSTM estimators additionally join per-spec stacked buckets for
    fused whole-fleet scoring.
    """

    def __init__(self, collection_dir: str):
        self.collection_dir = collection_dir
        self._lock = threading.Lock()
        # _models and _specs are COPY-ON-WRITE: loads replace the whole
        # dict under the lock, readers just dereference the attribute
        # (an atomic ref read) — the per-request serving path never
        # touches the lock, so a thousand concurrent requests can't
        # convoy behind it (nor behind the micro-batcher's per-batch
        # bucket lookup). Never mutate these dicts in place.
        self._models: Dict[str, Any] = {}
        self._specs: Dict[str, Any] = {}  # name -> spec (JAX models only)
        #: name -> ModelResolution (COW, same discipline as _models)
        self._resolutions: Dict[str, ModelResolution] = {}
        #: spec -> (names, stacked params, epoch stamped at build)
        self._stacked: Dict[Any, Tuple[List[str], Any, int]] = {}
        #: (spec, precision) -> (names, cast/quantized params, epoch):
        #: reduced-precision copies of the f32 buckets, cast ONCE at
        #: fleet load (serve.precision.cast_bucket_params) — the serve
        #: engine's precision ladder reads these per batch, never
        #: re-casts per request. Mutated only under the lock, like
        #: _stacked.
        self._cast_buckets: Dict[Tuple[Any, str], Tuple[List[str], Any, int]] = {}
        #: spec -> (names, FleetIngestPlan | None, epoch): the compiled
        #: preprocessing plan per spec bucket (gordo_tpu.ingest), built
        #: lazily like the buckets. None is a NEGATIVE verdict (some
        #: member's pipeline is not affine-compilable) and is cached too
        #: — probing an uncompilable fleet must not re-walk sklearn
        #: object graphs per request. Mutated only under the lock;
        #: epoch-stamped so hot-swap/DELETE invalidation is inherited.
        self._ingest_plans: Dict[Any, Tuple[List[str], Any, int]] = {}
        #: (spec, precision) -> precision-parity gate report (COW, same
        #: discipline as _models): the serve engine's governor caches
        #: pass/fail verdicts here, so gate state lives and dies with
        #: the revision fleet — a hot-swap or DELETE re-gates naturally.
        self._precision_states: Dict[Tuple[Any, str], Dict[str, Any]] = {}
        self._bucket_epoch = 0  # bumped on every membership change

    # -- single-model serving ------------------------------------------------

    def model(self, name: str) -> Any:
        """The loaded model for ``name`` (load-once, then resident)."""
        cached = self._models.get(name)  # lock-free: _models is COW
        if cached is not None:
            return cached

        model = serializer.load(os.path.join(self.collection_dir, name))
        estimator = _find_estimator(model)
        if estimator is not None and estimator.params_ is not None:
            # Device-resident parameters: every later predict skips the
            # host→device transfer the unpickled numpy params would pay.
            estimator.params_ = jax.device_put(estimator.params_)
        with self._lock:
            # Lost the load race: keep the first copy (single residency).
            existing = self._models.get(name)
            if existing is not None:
                return existing
            models = dict(self._models)
            models[name] = model
            self._models = models
            if estimator is not None and estimator.spec_ is not None:
                specs = dict(self._specs)
                specs[name] = estimator.spec_
                self._specs = specs
                self._stacked.pop(estimator.spec_, None)  # bucket grew; restack
                for key in [
                    k for k in self._cast_buckets if k[0] == estimator.spec_
                ]:
                    self._cast_buckets.pop(key, None)  # recast with the bucket
                self._ingest_plans.pop(estimator.spec_, None)  # replan too
                self._bucket_epoch += 1
        return model

    def resolution(self, name: str) -> ModelResolution:
        """The cached :class:`ModelResolution` for ``name`` — model,
        parsed metadata, tag lists, frequency, thresholds, alignment
        plans — built at most once per revision (lock-free COW read on
        the hot path, like :meth:`model`). Raises ``FileNotFoundError``
        when the artifacts are gone (the routes' 404 contract)."""
        cached = self._resolutions.get(name)  # lock-free: COW
        if cached is not None:
            return cached
        model = self.model(name)
        model_dir = os.path.join(self.collection_dir, name)
        metadata = serializer.load_metadata(model_dir)
        try:
            info = serializer.load_info(model_dir)
        except FileNotFoundError:
            info = {}
        resolution = ModelResolution(name, model, metadata, info)
        with self._lock:
            existing = self._resolutions.get(name)
            if existing is not None:
                return existing
            resolutions = dict(self._resolutions)
            resolutions[name] = resolution
            self._resolutions = resolutions
        return resolution

    def warm(self, names: Optional[List[str]] = None) -> List[str]:
        """Load every model in the revision dir (or ``names``); returns the
        names that loaded successfully."""
        if names is None:
            # list_model_dirs skips the builder's crash-safety droppings:
            # atomic-dump staging dirs (possibly half-written by a killed
            # build) and the build journal are never models.
            names = serializer.list_model_dirs(self.collection_dir)
        loaded = []
        for name in names:
            try:
                self.model(name)
                loaded.append(name)
            except Exception as exc:  # noqa: BLE001 - one bad artifact must
                # not abort warming the other 99 (same per-machine
                # isolation as fleet_scores)
                logger.warning(
                    "warm: could not load %s/%s: %r", self.collection_dir, name, exc
                )
        return loaded

    # -- fused fleet scoring -------------------------------------------------

    def spec_bucket(self, spec, precision: str = "f32") -> Tuple[List[str], Any]:
        """
        The (names, stacked device params) bucket for one spec (feedforward
        or LSTM), built from every loaded model of that spec. Restacked
        only when the bucket's membership changed since the last call. The
        stacking work (host round-trip of every member's params) runs
        OUTSIDE the store lock so concurrent single-model serving never
        stalls behind it.

        ``precision`` other than ``f32`` answers the bucket's cast
        (bf16) or weight-quantized (int8) copy, derived from the f32
        master bucket once per (spec, precision) and cached for the
        revision's lifetime (:meth:`_cast_bucket`).
        """
        from ..parallel.fleet import stack_member_params

        if precision and precision != "f32":
            return self._cast_bucket(spec, precision)
        with self._lock:
            cached = self._stacked.get(spec)
            epoch = self._bucket_epoch
            if cached is not None and cached[2] == epoch:
                # Hot path — one dict probe + an int compare. The
                # micro-batcher hits this once per fused batch while the
                # request threads churn; re-deriving membership here
                # (sort + dict build) measurably starves the dispatcher
                # of the GIL under load.
                return cached[0], cached[1]
            specs, models = self._specs, self._models  # COW snapshots
        names = sorted(n for n, s in specs.items() if s == spec)
        if cached is not None and cached[0] == names:
            with self._lock:
                if self._bucket_epoch == epoch:
                    self._stacked[spec] = (cached[0], cached[1], epoch)
            return cached[0], cached[1]
        if not names:
            raise KeyError(f"no loaded models with spec {spec}")

        class _P:  # stack_member_params wants .params carriers
            __slots__ = ("params",)

            def __init__(self, params):
                self.params = params

        host = [
            # gt-lint: disable=jax-device-sync -- one-time member-param
            # stacking at revision load, outside any program span by design
            _P(jax.device_get(_find_estimator(models[n]).params_)) for n in names
        ]
        stacked = jax.device_put(stack_member_params(host))
        with self._lock:
            # Concurrent stackers of the same membership write identical
            # content; a membership change since our snapshot just means
            # the next call restacks (membership is re-derived then).
            if self._bucket_epoch == epoch:
                self._stacked[spec] = (names, stacked, epoch)
        return names, stacked

    #: retained name from before LSTM buckets existed (r3 API)
    feedforward_bucket = spec_bucket

    def _cast_bucket(self, spec, precision: str) -> Tuple[List[str], Any]:
        """The reduced-precision copy of one spec bucket: cast/quantized
        from the f32 master ONCE per (spec, precision) per membership
        epoch. The cast work (a whole-tree device op) runs outside the
        lock, mirroring :meth:`spec_bucket`'s stacking discipline."""
        from ..serve.precision import cast_bucket_params

        with self._lock:
            cached = self._cast_buckets.get((spec, precision))
            epoch = self._bucket_epoch
            if cached is not None and cached[2] == epoch:
                return cached[0], cached[1]
        names, stacked = self.spec_bucket(spec)
        cast = cast_bucket_params(stacked, precision)
        with self._lock:
            # a membership change since our snapshot means the next call
            # recasts against the fresh f32 bucket (same rule as
            # spec_bucket's concurrent-stacker contract)
            if self._bucket_epoch == epoch:
                self._cast_buckets[(spec, precision)] = (names, cast, epoch)
        return names, cast

    def ingest_plan(self, spec):
        """The compiled preprocessing plan for one spec bucket
        (:class:`gordo_tpu.ingest.FleetIngestPlan`, bucket-name order),
        or None when any member's pipeline is not affine-compilable —
        the NEGATIVE verdict is cached per membership epoch too, so an
        uncompilable fleet costs one dict probe per request, not a
        sklearn object-graph walk. Plan extraction runs outside the
        lock, like every other bucket build."""
        from ..ingest import build_fleet_plan

        with self._lock:
            cached = self._ingest_plans.get(spec)
            epoch = self._bucket_epoch
            if cached is not None and cached[2] == epoch:
                return cached[1]
            specs, models = self._specs, self._models  # COW snapshots
        names = sorted(n for n, s in specs.items() if s == spec)
        if not names:
            return None
        plan = build_fleet_plan(
            [(n, models[n]) for n in names], spec.n_features
        )
        with self._lock:
            if self._bucket_epoch == epoch:
                self._ingest_plans[spec] = (names, plan, epoch)
        return plan

    # -- precision-parity gate state -----------------------------------------

    def precision_state(self, spec, precision: str) -> Optional[Dict[str, Any]]:
        """The cached precision-parity gate report for (spec,
        ``precision``), or None when ungated — INCLUDING when the
        bucket's membership changed since the verdict was taken (states
        are epoch-stamped like the cast buckets: a PASS gated on the
        old membership must not let a later-loaded member serve reduced
        unverified, and a racy FAIL must not stick forever). Lock-free
        COW read — this sits on the per-request serving path (the
        engine's governor probes it per batched request)."""
        entry = self._precision_states.get((spec, precision))
        if entry is None:
            return None
        report, epoch = entry
        return report if epoch == self._bucket_epoch else None

    def set_precision_state(
        self,
        spec,
        precision: str,
        report: Dict[str, Any],
        epoch: Optional[int] = None,
    ):
        """Record a gate verdict (COW replace under the lock, like every
        other serving map), stamped with the membership epoch the
        verdict was EVALUATED at (``epoch``; default: current) — a
        verdict taken against an older membership must read as absent,
        not as a fresh PASS/FAIL. The state is revision-fleet-scoped by
        construction: a hot-swapped or invalidated revision drops its
        fleet object, verdicts and all, and the replacement re-gates."""
        with self._lock:
            states = dict(self._precision_states)
            states[(spec, precision)] = (
                report,
                self._bucket_epoch if epoch is None else epoch,
            )
            self._precision_states = states

    def precision_reports(self) -> List[Dict[str, Any]]:
        """Every LIVE cached gate report (current-epoch verdicts only —
        for the engine stats / fleet-status surface)."""
        epoch = self._bucket_epoch
        return [
            report
            for report, stamped in self._precision_states.values()
            if stamped == epoch
        ]

    def loaded_specs(self) -> Dict[str, Any]:
        """The name -> spec map of the loaded JAX models. The returned
        dict is a COW snapshot — treat it as read-only (no per-call copy:
        this sits on the per-request serving path)."""
        return self._specs

    def resident_bytes(self) -> Dict[str, int]:
        """Estimated bytes this fleet keeps resident: per-member params,
        the fused f32 bucket stacks, and the reduced-precision cast
        copies. An *estimate* (``size * itemsize`` over array leaves;
        non-array leaves and host-side pipeline objects are not
        counted) — the fleet-status / Prometheus capacity signal, not an
        allocator audit. Lock-free: reads the COW maps; ``_stacked`` /
        ``_cast_buckets`` values are replaced whole, so a concurrent
        restack at worst skews one bucket."""

        def _tree_bytes(tree: Any) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                try:
                    total += int(leaf.size) * int(leaf.dtype.itemsize)
                except (AttributeError, TypeError):
                    continue  # non-array leaf (scalars, None, strings)
            return total

        model_bytes = 0
        models = self._models  # COW snapshot
        for model in models.values():
            estimator = _find_estimator(model)
            if estimator is not None and getattr(estimator, "params_", None) is not None:
                model_bytes += _tree_bytes(estimator.params_)
        stacked_bytes = sum(
            _tree_bytes(params) for (_, params, _) in list(self._stacked.values())
        )
        cast_bytes = sum(
            _tree_bytes(params)
            for (_, params, _) in list(self._cast_buckets.values())
        )
        ingest_bytes = sum(
            plan.nbytes
            for (_, plan, _) in list(self._ingest_plans.values())
            if plan is not None
        )
        return {
            "models": len(models),
            "model_bytes": model_bytes,
            "stacked_bytes": stacked_bytes,
            "cast_bytes": cast_bytes,
            "ingest_bytes": ingest_bytes,
            "total_bytes": model_bytes
            + stacked_bytes
            + cast_bytes
            + ingest_bytes,
        }

    def fleet_scores(
        self, inputs: Dict[str, Any]
    ) -> Tuple[Dict[str, Tuple[np.ndarray, np.ndarray]], Dict[str, Exception]]:
        """
        Score many models in one device program per spec bucket:
        ``inputs[name] -> X`` (raw model-space frames/arrays; host pipeline
        transformers are applied here) returns ``(scores, errors)`` where
        ``scores[name] -> (reconstruction, per-row mse)`` and ``errors``
        records per-machine failures (a broken model never takes the batch
        down). Feedforward AND windowed LSTM models take fused per-spec
        bucket paths; any others fall back to their own predict.
        """
        errors: Dict[str, Exception] = {}
        loadable = []
        for name in inputs:
            try:
                self.model(name)  # ensure loaded + bucketed
                loadable.append(name)
            except Exception as exc:  # noqa: BLE001 - per-machine isolation
                logger.warning("fleet_scores: could not load %s: %r", name, exc)
                if isinstance(exc, FileNotFoundError):
                    errors[name] = exc  # routes map it to a plain 404
                else:
                    load_error = ModelLoadError(name)
                    load_error.__cause__ = exc
                    errors[name] = load_error

        specs = self.loaded_specs()
        by_spec: Dict[Any, List[str]] = {}
        by_lstm_spec: Dict[Any, List[str]] = {}
        fallback: List[str] = []
        for name in loadable:
            spec = specs.get(name)
            if isinstance(spec, FeedForwardSpec):
                by_spec.setdefault(spec, []).append(name)
            elif getattr(spec, "windowed", False):
                # every windowed spec (LSTMs, backbones) scores through
                # the on-device window-gather program
                by_lstm_spec.setdefault(spec, []).append(name)
            else:
                fallback.append(name)

        out: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}

        def mse_vs_raw(prediction: np.ndarray, raw: np.ndarray) -> np.ndarray:
            # Reconstructions live in raw target space (host transformers
            # only feed the estimator input), so error is vs the raw rows,
            # tail-aligned for windowed models' shorter outputs.
            aligned = raw[len(raw) - len(prediction):]
            width = min(prediction.shape[-1], aligned.shape[-1])
            return ((prediction[:, :width] - aligned[:, :width]) ** 2).mean(axis=-1)

        for spec, names in by_spec.items():
            names, member_params, transformed = self._bucket_request(
                spec, names, inputs, errors
            )
            if not names:
                continue
            b_max = max(arr.shape[0] for arr in transformed.values())
            X = np.zeros((len(names), b_max, spec.n_features), np.float32)
            for i, n in enumerate(names):
                X[i, : transformed[n].shape[0]] = transformed[n]
            recon = np.asarray(fleet_forward(spec, member_params, X))
            for i, n in enumerate(names):
                b = transformed[n].shape[0]
                r = recon[i, :b]
                out[n] = (r, mse_vs_raw(r, np.asarray(inputs[n], np.float32)))
        for spec, names in by_lstm_spec.items():
            # machines whose spec trains alone (no member axis, or a state
            # larger than the planner's cap) score alone too: one program
            # of one shape, run once a machine
            rosters = [[n] for n in names] if trains_alone(spec) else [names]
            for roster in rosters:
                self._score_lstm_bucket(
                    spec, roster, inputs, out, errors, mse_vs_raw
                )
        for n in fallback:
            try:
                model = self._models[n]
                prediction = np.asarray(model.predict(inputs[n]))
                out[n] = (
                    prediction,
                    mse_vs_raw(prediction, np.asarray(inputs[n], np.float32)),
                )
            except Exception as exc:  # noqa: BLE001 - per-machine isolation
                logger.warning("fleet_scores: predict failed for %s: %r", n, exc)
                errors[n] = exc
        return out, errors

    def _bucket_request(self, spec, names, inputs, errors):
        """Shared bucket-request staging: sort into bucket order, apply
        host transformers with per-machine error isolation, and gather the
        requested members' stacked params (whole-bucket requests — the
        replay/dashboard pattern — serve straight off the resident stack)."""
        from ..ingest import compiled_enabled

        names = sorted(names)
        bucket_names, stacked = self.spec_bucket(spec)
        rows = {n: i for i, n in enumerate(bucket_names)}
        plan = self.ingest_plan(spec) if compiled_enabled() else None
        transformed = {}
        for n in names:
            try:
                if plan is not None and plan.identity:
                    # the compiled-plan verdict for a bare-estimator
                    # bucket: the pipeline walk IS a float32 cast
                    transformed[n] = np.asarray(inputs[n], np.float32)
                elif plan is not None:
                    # vectorized composed-affine staging off the plan's
                    # host copy — one fused multiply-add instead of a
                    # per-transformer sklearn pass
                    i = rows[n]
                    transformed[n] = np.asarray(
                        np.asarray(inputs[n], np.float32)
                        * plan.host_scale[i]
                        + plan.host_offset[i],
                        np.float32,
                    )
                else:
                    transformed[n] = _host_transform(self._models[n], inputs[n])
            except Exception as exc:  # noqa: BLE001 - per-machine isolation
                logger.warning("fleet_scores: transform failed for %s: %r", n, exc)
                errors[n] = exc
        names = [n for n in names if n in transformed]
        if not names:
            return [], None, {}
        if names == bucket_names:
            member_params = stacked
        else:
            member_params = jax.tree_util.tree_map(
                lambda a: a[np.asarray([rows[n] for n in names])], stacked
            )
        return names, member_params, transformed

    def _score_lstm_bucket(self, spec, names, inputs, out, errors, mse_vs_raw):
        """
        Fused LSTM scoring: every member's raw series stays ``[b, F]`` and
        windows are gathered on device per scan batch
        (parallel.fleet.fleet_windowed_predict_program) — one device
        program for the whole bucket, same as the feedforward path.
        Window counts honor each estimator's lookahead (the model-offset
        contract), which is per-member data, not part of the compiled
        shape.
        """
        from ..parallel.fleet import fleet_windowed_predict_program

        names, member_params, transformed = self._bucket_request(
            spec, names, inputs, errors
        )
        if not names:
            return
        lookback = spec.lookback_window
        counts = {}
        for n in names:
            estimator = _find_estimator(self._models[n])
            lookahead = getattr(estimator, "lookahead", 0)
            count = transformed[n].shape[0] - lookback - lookahead + 1
            if count <= 0:
                errors[n] = ValueError(
                    f"series of {transformed[n].shape[0]} rows too short for "
                    f"lookback {lookback} (lookahead {lookahead})"
                )
            else:
                counts[n] = count
        kept = [n for n in names if n in counts]
        if not kept:
            return
        if kept != names:
            keep_rows = np.asarray([names.index(n) for n in kept])
            member_params = jax.tree_util.tree_map(
                lambda a: a[keep_rows], member_params
            )
        b_max = max(transformed[n].shape[0] for n in kept)
        # series shorter than one window would make even the zero-padded
        # gather read out of bounds
        b_max = max(b_max, lookback)
        batch = windowed_scoring_batch(spec)  # windows a scan step
        nv_max = -(-max(counts.values()) // batch) * batch
        series = np.zeros((len(kept), b_max, spec.n_features), np.float32)
        order = np.zeros((len(kept), nv_max), np.int32)
        for i, n in enumerate(kept):
            series[i, : transformed[n].shape[0]] = transformed[n]
            order[i, : counts[n]] = np.arange(counts[n])
        predictions = np.asarray(
            fleet_windowed_predict_program(spec, batch)(
                member_params, series, order
            )
        )
        for i, n in enumerate(kept):
            prediction = predictions[i, : counts[n]]
            out[n] = (
                prediction,
                mse_vs_raw(prediction, np.asarray(inputs[n], np.float32)),
            )


def use_pallas() -> bool:
    """Fused Pallas serving kernel: on by default on TPU backends, off
    elsewhere and under ``GORDO_TPU_DISABLE_PALLAS``."""
    # env_bool: a literal `GORDO_TPU_DISABLE_PALLAS=0` now reads as
    # enabled-Pallas instead of silently disabling it (truthy-string bug)
    if env_bool("GORDO_TPU_DISABLE_PALLAS", False):
        return False
    return jax.default_backend() == "tpu"


def serving_backend(precision: str = "f32") -> str:
    """The fused-program backend for one serving precision: the Pallas
    kernel serves the f32 path on TPU; reduced-precision programs run
    the XLA vmapped forward everywhere (bf16 hits the MXU natively
    through XLA; a reduced-precision Pallas kernel is a follow-up —
    dtype tiling differs, see the Pallas guide's tiling table)."""
    if precision and precision != "f32":
        return "xla"
    return "pallas" if use_pallas() else "xla"


def fleet_forward(spec: FeedForwardSpec, stacked_params, X: np.ndarray):
    """
    The fused fleet forward ``X[M, B, F] -> [M, B, F_out]``: Pallas kernel
    on TPU (whole layer stack per grid step, activations in VMEM —
    ops/pallas_dense.py), XLA vmap elsewhere. Both paths share ONE cached
    program table keyed by (spec, backend, precision) so serving requests
    hit a compiled program and cache growth is observable in one place
    (``program_cache_stats`` / the ``gordo_server_program_cache_size``
    Prometheus gauge).
    """
    backend = serving_backend()
    return _fleet_forward_program(spec, backend, False, "f32")(stacked_params, X)


def fleet_forward_gather(
    spec: FeedForwardSpec,
    stacked_params,
    indices: np.ndarray,
    X: np.ndarray,
    precision: str = "f32",
    ingest=None,
):
    """
    The fused gather+forward the micro-batcher runs:
    ``(bucket[N, ...], indices[M], X[M, B, F]) -> [M, B, F_out]``, where
    ``indices`` picks each batch member's row out of the revision's FULL
    resident bucket INSIDE the jitted program. One device dispatch per
    batch — gathering on the host instead (a ``tree_map`` of fancy
    indexing) costs one tiny device program per parameter leaf, which at
    micro-batch rates dominates the fused forward itself. The jit
    signature includes the bucket's member count, which is fixed per
    revision, so the executable count per spec stays bounded by the serve
    shape ladder (now ``× |precisions in use|``).

    ``precision`` selects the reduced-precision program variant; the
    caller passes the MATCHING bucket (``spec_bucket(spec, precision)``)
    — bf16 weights for the bf16 program, the quantized pytree for int8.
    Output is float32 at every precision (the dtype contract).

    ``ingest`` — the device-resident preprocessing plan as a
    ``(scale[N, F], offset[N, F])`` pair (``RevisionFleet.ingest_plan``)
    — selects the INGEST program variant: ``X`` arrives as raw float32
    wire rows and the compiled prologue gathers each member's plan row
    with the same ``indices``, applies ``X*scale+offset`` in float32,
    then casts to the precision's payload dtype before the fused
    forward. None (identity plans included — see
    ``gordo_tpu.ingest.plan``) runs the classic pre-transformed-payload
    program, bit-identical to what it computed before plans existed.
    """
    precision = precision or "f32"
    backend = serving_backend(precision)
    if ingest is not None:
        scale, offset = ingest
        return _fleet_forward_program(spec, backend, True, precision, True)(
            stacked_params, indices, X, scale, offset
        )
    return _fleet_forward_program(spec, backend, True, precision)(
        stacked_params, indices, X
    )


#: keys ever handed to ``_fleet_forward_program`` — lru_cache has no key
#: iteration API, and ``program_cache_stats`` needs the live entries to
#: sum their per-shape executable counts
_program_cache_keys: set = set()


def _fleet_forward_program(
    spec: FeedForwardSpec,
    backend: str,
    gather: bool,
    precision: str = "f32",
    ingest: bool = False,
):
    _program_cache_keys.add((spec, backend, gather, precision, ingest))
    return _build_fleet_forward_program(spec, backend, gather, precision, ingest)


@lru_cache(maxsize=None)
def _build_fleet_forward_program(
    spec: FeedForwardSpec,
    backend: str,
    gather: bool = False,
    precision: str = "f32",
    ingest: bool = False,
):
    """The jitted fused-forward entry for one (spec, backend[, gather,
    precision]). The lru entry holds the jit wrapper; XLA compiles one
    executable per input shape INSIDE it (counted by
    ``program_cache_stats``)."""
    if backend == "pallas":
        from ..ops.pallas_dense import fleet_feedforward_pallas

        fused = lambda params, X: fleet_feedforward_pallas(spec, params, X)  # noqa: E731
    elif precision == "int8":
        from ..serve.precision import forward_feedforward_quantized

        fused = jax.vmap(
            lambda p, x: forward_feedforward_quantized(spec, p, x)
        )
    else:
        from ..models.nn import forward_fn_for

        forward = forward_fn_for(spec)
        if precision == "bf16":
            # the serving spec computes in bf16 whatever the training
            # compute_dtype was; the forward's own contract keeps the
            # OUTPUT float32
            from dataclasses import replace

            run_spec = replace(spec, compute_dtype="bfloat16")
        else:
            run_spec = spec
        fused = jax.vmap(lambda p, x: forward(run_spec, p, x)[0])
    if gather:
        if ingest:
            from ..serve.precision import payload_dtype

            dtype = payload_dtype(precision)

            def run_ingest(params, indices, X, scale, offset):
                member = jax.tree_util.tree_map(lambda a: a[indices], params)
                # the fused preprocessing prologue: raw float32 wire rows
                # through each member's composed affine plan, then into
                # the precision's payload dtype — the same tensor the
                # pre-transformed payload program would have received
                s = scale[indices][:, None, :]
                o = offset[indices][:, None, :]
                Xp = X.astype(jax.numpy.float32) * s + o
                return fused(member, Xp.astype(dtype))

            return jax.jit(run_ingest)

        def run(params, indices, X):
            member = jax.tree_util.tree_map(lambda a: a[indices], params)
            return fused(member, X)

        return jax.jit(run)
    return jax.jit(fused)


def program_cache_stats() -> Dict[str, Any]:
    """Serving program-cache sizes: ``programs`` is the number of cached
    (spec, backend, precision) jit entries, ``signatures`` the number of
    XLA executables compiled inside them (distinct argument shapes) —
    the number that must stay bounded by the serve shape ladder —
    and ``by_backend`` the same executables split by serving backend
    (``pallas``/``xla``), so a surface can tell a compiled kernel from
    the XLA forward."""
    signatures = 0
    by_precision: Dict[str, int] = {}
    by_backend: Dict[str, int] = {}
    for (spec, backend, gather, precision, ingest) in list(_program_cache_keys):
        by_precision[precision] = by_precision.get(precision, 0) + 1
        compiled = _build_fleet_forward_program(
            spec, backend, gather, precision, ingest
        )._cache_size()
        signatures += compiled
        by_backend[backend] = by_backend.get(backend, 0) + compiled
    return {
        "programs": _build_fleet_forward_program.cache_info().currsize,
        "signatures": signatures,
        "by_precision": by_precision,
        "by_backend": by_backend,
    }


class FleetModelStore:
    """LRU of :class:`RevisionFleet`s keyed by (real) revision directory.

    ``N_CACHED_REVISIONS`` (env, default 2) bounds how many *revisions*
    stay resident — the model axis within a revision is never evicted,
    which is the point: the reference's pressure point was per-model
    eviction, not revision count.

    Lifecycle extensions (``gordo_tpu.lifecycle``): :meth:`route`
    resolves a requested collection dir through the hot-swap redirect a
    promotion installed (:meth:`swap`) and the canary traffic slice
    (:meth:`set_canary`) — requests route ONCE, at revision-resolution
    time, so one request never mixes base and canary artifacts (model
    from one, params from the other). A swap never touches an existing
    :class:`RevisionFleet`: in-flight work pinned to the old fleet
    object keeps scoring its device-resident snapshot (the same
    contract the DELETE-revision race relies on), while requests routed
    after the swap resolve the new — pre-warmed — fleet.
    """

    def __init__(self, max_revisions: Optional[int] = None):
        if max_revisions is None:
            # Validated, never trusted: this constructor runs at module
            # import (the process-wide STORE below), so a malformed env
            # var must degrade to the default, not kill every worker at
            # boot.
            max_revisions = env_int("N_CACHED_REVISIONS", 2)
            if max_revisions < 1:
                logger.warning(
                    "N_CACHED_REVISIONS=%d is not a positive revision "
                    "count; using 2",
                    max_revisions,
                )
                max_revisions = 2
        self.max_revisions = max_revisions
        self._lock = threading.Lock()
        self._revisions: "OrderedDict[str, RevisionFleet]" = OrderedDict()
        #: lock-free fast path for the overwhelmingly common case of every
        #: request hitting the same revision: one atomic tuple read
        #: instead of realpath() syscalls + the store lock + an
        #: OrderedDict reorder PER REQUEST (all three are GIL-handoff
        #: points that convoy under concurrent serving load)
        self._mru: Optional[Tuple[str, RevisionFleet]] = None
        #: hot-swap redirects: requested dir -> served dir. Mutated only
        #: under the lock; read lock-free (dict.get is atomic under the
        #: GIL) on the per-request routing path.
        self._redirects: Dict[str, str] = {}
        #: canary slice: (source dir, canary dir, every-nth period) —
        #: one atomic tuple read per routed request; None in steady
        #: state. The tick is intentionally unlocked: under concurrent
        #: load the slice is approximate (lost increments skew it a
        #: request or two), which is fine for traffic splitting and
        #: keeps the hot path lock-free.
        self._canary: Optional[Tuple[str, str, int]] = None
        self._canary_tick = 0

    # -- lifecycle routing --------------------------------------------------

    @staticmethod
    def _route_key(collection_dir: str) -> str:
        """Routing keys are normpath'd strings: the env var may carry a
        trailing slash while the supervisor/restore path installs
        normalized sources — a cosmetic difference must not silently
        disable a recorded promotion or a canary slice. (normpath, not
        realpath: no syscalls on the per-request path.)"""
        return os.path.normpath(collection_dir)

    def route(self, collection_dir: str) -> str:
        """The directory a request for ``collection_dir`` should serve
        from, after the hot-swap redirect and the canary slice. Resolved
        once per request (at revision resolution) so every artifact the
        request touches — model, metadata, params — comes from ONE
        revision."""
        key = self._route_key(collection_dir)
        canary = self._canary
        if canary is not None and canary[0] == key:
            self._canary_tick += 1
            if self._canary_tick % canary[2] == 0:
                return canary[1]
        return self._redirects.get(key, collection_dir)

    def swap(
        self, collection_dir: str, new_dir: str, warm: bool = True
    ) -> RevisionFleet:
        """Zero-downtime hot swap: requests for ``collection_dir`` serve
        ``new_dir`` from now on. The new fleet is loaded (and by default
        warmed) BEFORE the redirect lands, so no request ever waits on
        cold artifact loads; requests already in flight keep the fleet
        object they resolved — nothing is dropped or torn. Swapping a
        dir onto itself removes the redirect (rollback to disk truth)."""
        fleet = self._ensure_fleet(new_dir, warm=warm)
        key = self._route_key(collection_dir)
        with self._lock:
            if os.path.realpath(new_dir) == os.path.realpath(collection_dir):
                self._redirects.pop(key, None)
            else:
                self._redirects[key] = new_dir
            canary = self._canary
            if canary is not None and canary[0] == key:
                self._canary = None
            # the swapped-in dir is about to be the hottest key
            self._mru = (new_dir, fleet)
        return fleet

    def set_canary(
        self,
        collection_dir: str,
        canary_dir: str,
        fraction: float,
        warm: bool = True,
    ) -> RevisionFleet:
        """Route ``~fraction`` of the traffic for ``collection_dir`` to
        ``canary_dir`` (every Nth routed request, N = round(1/fraction)
        — deterministic, no per-request RNG). The canary fleet is
        pre-warmed before any traffic lands on it."""
        if not 0.0 < fraction <= 1.0:
            raise ValueError(f"canary fraction must be in (0, 1]: {fraction}")
        fleet = self._ensure_fleet(canary_dir, warm=warm)
        period = max(1, int(round(1.0 / fraction)))
        with self._lock:
            self._canary = (self._route_key(collection_dir), canary_dir, period)
        return fleet

    def clear_canary(self, collection_dir: Optional[str] = None) -> None:
        """Stop canary routing (for ``collection_dir``, or whatever is
        canarying); in-flight canary-routed requests finish against the
        still-resident canary fleet."""
        with self._lock:
            canary = self._canary
            if canary is not None and (
                collection_dir is None
                or canary[0] == self._route_key(collection_dir)
            ):
                self._canary = None

    def canary_status(self) -> Optional[Dict[str, Any]]:
        canary = self._canary
        if canary is None:
            return None
        return {
            "source": canary[0],
            "canary": canary[1],
            "fraction": 1.0 / canary[2],
        }

    def revision_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-resident-revision byte estimates, keyed by the revision
        dir's basename (``RevisionFleet.resident_bytes``; the key set is
        bounded by ``N_CACHED_REVISIONS``). The fleet-status ``serving``
        section and the ``gordo_store_revision_bytes`` gauge read this."""
        with self._lock:
            revisions = list(self._revisions.items())
        return {
            os.path.basename(key) or key: fleet.resident_bytes()
            for key, fleet in revisions
        }

    def _rerank_mru_locked(self) -> None:
        """Re-rank the lock-free fast path's fleet before any eviction
        decision (caller holds the lock): requests served through
        ``_mru`` never refresh their LRU slot, so the hottest revision
        can look least-recently-used — evicting it would force every
        fast-path request onto a cold reload."""
        mru = self._mru
        if mru is None:
            return
        for mru_key, mru_fleet in self._revisions.items():
            if mru_fleet is mru[1]:
                self._revisions.move_to_end(mru_key)
                break

    def _ensure_fleet(self, collection_dir: str, warm: bool) -> RevisionFleet:
        """The ONE get-or-create path for resident fleets — request
        path (:meth:`fleet`) and lifecycle path (swap/set_canary) share
        it, so eviction and MRU re-rank behavior cannot drift apart.
        Model loads (``warm``) run OUTSIDE the store lock, like every
        other load path. The re-rank walk is O(max_revisions)."""
        key = os.path.realpath(collection_dir)
        with self._lock:
            fleet = self._revisions.get(key)
            if fleet is None:
                self._rerank_mru_locked()
                fleet = RevisionFleet(key)
                self._revisions[key] = fleet
                while len(self._revisions) > self.max_revisions:
                    evicted_key, _ = self._revisions.popitem(last=False)
                    logger.info("Evicting served revision %s", evicted_key)
            else:
                self._revisions.move_to_end(key)
        if warm:
            fleet.warm()
        return fleet

    def fleet(self, collection_dir: str) -> RevisionFleet:
        mru = self._mru
        if mru is not None and mru[0] == collection_dir:
            return mru[1]
        fleet = self._ensure_fleet(collection_dir, warm=False)
        with self._lock:
            self._mru = (collection_dir, fleet)
        return fleet

    def get_model(self, collection_dir: str, name: str) -> Any:
        return self.fleet(collection_dir).model(name)

    def invalidate(self, collection_dir: str):
        key = os.path.realpath(collection_dir)
        with self._lock:
            self._mru = None  # conservatively, whatever alias it holds
            self._revisions.pop(key, None)
            # Routing that TARGETS the invalidated dir is stale too: a
            # deleted canary must stop taking traffic, and a redirect
            # onto a deleted revision must fall back to disk truth.
            # Routing FROM it survives — a redirect is serving state,
            # not a cache of the source dir's content.
            canary = self._canary
            if canary is not None and os.path.realpath(canary[1]) == key:
                self._canary = None
            for source, target in list(self._redirects.items()):
                if os.path.realpath(target) == key:
                    del self._redirects[source]

    def clear(self):
        with self._lock:
            self._mru = None
            self._revisions.clear()
            self._redirects.clear()
            self._canary = None


#: Process-wide store (gunicorn gthread workers share it per process, like
#: the reference's module-level lru_cache).
STORE = FleetModelStore()
