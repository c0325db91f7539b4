"""
Analytic bucket cost model with trace-fitted correction factors.

Per-program TPU cost is predictable from static features plus a small
calibration set (the learned-performance-model line of work, PAPERS.md).
This module is the smallest useful instance of that recipe:

- **static features**: parameter count and padded training FLOPs derived
  from the spec geometry alone (:func:`spec_param_count`,
  :func:`spec_flops_per_sample`) — the planner never traces or compiles
  anything to cost a candidate bucket;
- **calibration**: :func:`calibrate` fits per-program correction factors
  from the ``device_program`` spans PR 3's telemetry already records in
  ``build_trace.jsonl`` (first-call-per-signature spans are compiles,
  the rest steady-state runs), and persists them as a versioned
  ``cost_table.json``.

Absolute accuracy is NOT the point — bucket *ranking* is. The packer
only ever compares candidate buckets of the same fleet against each
other, so a constant-factor error cancels; the calibration exists to
keep the compile-vs-run trade (the compile-budget knob) honest on the
actual backend.
"""

import json
import logging
import math
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..models.spec import ModelSpec
from ..utils.env import env_bool

logger = logging.getLogger(__name__)

#: canonical calibrated-table filename (beside the trace it was fit from)
COST_TABLE_FILE = "cost_table.json"

#: cost_table.json schema version — bump on shape changes so stale
#: tables are rejected instead of silently misread
COST_TABLE_VERSION = 1

#: master switch for the LEARNED performance model (PR 20): when on, a
#: cost table carrying a fitted ``learned`` section answers predictions
#: from its log-linear regressors (in-domain) instead of the analytic
#: formula. Off (the default) the learned section is inert — plans and
#: ladder choices are byte-identical to the analytic model's.
PERFMODEL_ENV = "GORDO_TPU_PERFMODEL"

#: ``learned`` section schema version inside cost_table.json — the
#: section versions independently of the table (an old table with no
#: section stays loadable; a future section shape downgrades to the
#: analytic fallback with a warning instead of rejecting the table)
LEARNED_VERSION = 1

#: the shared feature vocabulary: the FIT side (gordo_tpu.perfmodel)
#: and the EVAL side (this module) must agree on the vector, and the
#: layering contract forbids planner->perfmodel imports — so the
#: vocabulary lives here, at the bottom, and perfmodel reads it from
#: below exactly like serve reads PRECISION_ALIASES
LEARNED_FEATURES: Tuple[str, ...] = (
    "log_flops_per_sample",
    "log_members",
    "log_rows",
    "log_epochs",
    "bf16",
    "int8",
)

#: prediction targets a learned section may carry, with their units
LEARNED_TARGETS: Tuple[str, ...] = ("device_ms", "compile_ms", "hbm_bytes")

#: extrapolation slack in log space around the training corpus's
#: per-feature [lo, hi] box: ~5x beyond the largest trained shape still
#: answers learned, further falls back analytic (a regressor fit on
#: 8-member buckets has no business costing a 4096-member one)
LEARNED_DOMAIN_SLACK = 1.6

#: Adam keeps params + grads + two moment vectors resident per member
_OPTIMIZER_COPIES = 4

#: backward pass ≈ 2x the forward FLOPs (grad wrt inputs + weights)
_TRAIN_FLOP_FACTOR = 3.0

#: resident WEIGHT bytes per element at each serving precision (the
#: serve engine's precision ladder): int8 weight-only quantization
#: additionally keeps a per-channel f32 scale, accounted separately in
#: :meth:`CostModel.serve_weight_bytes`
PRECISION_WEIGHT_BYTES: Dict[str, int] = {"f32": 4, "bf16": 2, "int8": 1}

#: activation/compute bytes per element: int8 serving runs its
#: activations in bf16 (weight-only quantization), so its compute width
#: is bf16's
PRECISION_COMPUTE_BYTES: Dict[str, int] = {"f32": 4, "bf16": 2, "int8": 2}

#: THE canonical precision-alias table. It lives HERE (not in
#: gordo_tpu.serve.precision, which re-imports it) because the layering
#: contract forbids planner→serve imports even lazily — the cost model
#: is the lowest layer that speaks precision, so it owns the vocabulary
#: and the serve package reads it from below.
PRECISION_ALIASES: Dict[str, str] = {
    "f32": "f32", "fp32": "f32", "float32": "f32",
    "bf16": "bf16", "bfloat16": "bf16",
    "int8": "int8", "i8": "int8", "w8": "int8",
}

#: analytic default per-precision step-time factors (shared by the
#: CostTable field default and the legacy-table load path)
DEFAULT_PRECISION_FACTORS: Dict[str, float] = {"bf16": 0.6, "int8": 0.55}


def perfmodel_enabled() -> bool:
    """The ``GORDO_TPU_PERFMODEL`` master switch (default off)."""
    return env_bool(PERFMODEL_ENV, False)


def learned_feature_vector(
    flops_per_sample: float,
    members: int,
    rows: int,
    epochs: int = 1,
    precision: Optional[str] = None,
) -> List[float]:
    """The :data:`LEARNED_FEATURES` vector for one program shape — the
    log-linear regressor's input, shared verbatim by the fit side
    (``gordo_tpu.perfmodel``) and this module's evaluation.

    >>> [round(v, 3) for v in learned_feature_vector(100.0, 8, 512)]
    [4.615, 2.079, 6.238, 0.0, 0.0, 0.0]
    """
    prec = normalize_precision(precision)
    return [
        math.log(max(float(flops_per_sample), 0.0) + 1.0),
        math.log(max(int(members), 1)),
        math.log(max(int(rows), 1)),
        math.log(max(int(epochs), 1)),
        1.0 if prec == "bf16" else 0.0,
        1.0 if prec == "int8" else 0.0,
    ]


def validate_learned_section(doc: object) -> Optional[dict]:
    """A usable ``learned`` section dict, or None (with ONE warning) for
    anything malformed — a truncated/mis-versioned/hand-edited section
    must downgrade to the analytic fallback, never traceback in the
    planner, the serve engine, or the lifecycle supervisor."""
    if doc is None:
        return None
    try:
        if not isinstance(doc, dict):
            raise ValueError(f"learned section is {type(doc).__name__}, not dict")
        version = int(doc.get("version", 0))
        if version != LEARNED_VERSION:
            raise ValueError(
                f"learned section version {version} != supported "
                f"{LEARNED_VERSION}"
            )
        features = tuple(str(f) for f in (doc.get("features") or ()))
        if features != LEARNED_FEATURES:
            raise ValueError(
                f"learned feature vocabulary {features!r} != "
                f"{LEARNED_FEATURES!r}"
            )
        width = len(LEARNED_FEATURES)
        targets = doc.get("targets")
        if not isinstance(targets, dict):
            raise ValueError("learned section carries no targets map")
        for target, programs in targets.items():
            if target not in LEARNED_TARGETS:
                raise ValueError(f"unknown learned target {target!r}")
            if not isinstance(programs, dict):
                raise ValueError(f"target {target!r} is not a program map")
            for program, entry in programs.items():
                coef = [float(c) for c in entry["coef"]]
                lo = [float(v) for v in entry["lo"]]
                hi = [float(v) for v in entry["hi"]]
                if len(coef) != width + 1 or len(lo) != width or len(hi) != width:
                    raise ValueError(
                        f"model {target}/{program} has wrong arity"
                    )
                if not all(math.isfinite(c) for c in coef):
                    raise ValueError(
                        f"model {target}/{program} has non-finite coefficients"
                    )
        return doc
    except (TypeError, ValueError, KeyError) as exc:
        logger.warning(
            "Ignoring unusable learned section in cost table (%s); "
            "falling back to the analytic model",
            exc,
        )
        return None


def normalize_precision(precision: Optional[str]) -> str:
    """Canonical precision key (``float32``→``f32``, ``bfloat16``→
    ``bf16``); unknown/empty values cost as f32 — the conservative
    (widest) estimate."""
    if not precision:
        return "f32"
    return PRECISION_ALIASES.get(str(precision).strip().lower(), "f32")


def compute_precision(spec: ModelSpec) -> str:
    """The precision feature of a spec's TRAINING programs, derived from
    its ``compute_dtype`` (bf16 compute halves activation traffic even
    though master params stay f32 — models/nn.py dtype contract)."""
    return normalize_precision(getattr(spec, "compute_dtype", "float32"))


def spec_param_count(spec: ModelSpec) -> int:
    """Trainable parameter count from the spec geometry alone: the spec
    answers (``ModelSpec.param_count``). 0 for a spec that cannot say —
    callers treat it as "cost unknown, keep the member in its own group"."""
    return int(spec.param_count()) if isinstance(spec, ModelSpec) else 0


def spec_state_bytes(spec: ModelSpec) -> int:
    """Bytes one member's training state holds whatever its batch:
    float32 weights, gradients and the optimizer's two moments."""
    return 4 * _OPTIMIZER_COPIES * spec_param_count(spec)


def spec_flops_per_sample(spec: ModelSpec) -> float:
    """Forward-pass FLOPs for ONE sample (one window for windowed specs):
    ``ModelSpec.flops_per_sample``, whose fallback is the dense-layer
    identity of ~2 FLOPs a parameter a sample."""
    if isinstance(spec, ModelSpec):
        return float(spec.flops_per_sample())
    return 2.0 * spec_param_count(spec)


@dataclass
class CostTable:
    """Versioned correction factors fit by :func:`calibrate`.

    ``run_factors``/``compile_factors`` map program name (``fleet_fit``,
    ``fleet_windowed_fit``, ...) to a multiplicative correction on the
    analytic estimate; unseen programs fall back to 1.0. ``throughput``
    and ``compile_per_flop`` are the analytic baseline constants the
    factors correct — persisted so a table is self-contained.
    """

    #: sustained training throughput (FLOP/s) the analytic model divides
    #: by; deliberately conservative-CPU-ish so an UNcalibrated model
    #: still ranks buckets sanely on the test backend
    throughput: float = 2.0e9
    #: seconds of XLA compile per traced FLOP-per-sample unit, plus a
    #: fixed per-program floor — compiles scale with program complexity
    #: (op count ~ layer count ~ flops/sample), not with data volume
    compile_per_flop: float = 2.0e-7
    compile_floor_s: float = 0.35
    #: per-program-dispatch fixed overhead (host dispatch + fetch)
    dispatch_s: float = 0.01
    run_factors: Dict[str, float] = field(default_factory=dict)
    compile_factors: Dict[str, float] = field(default_factory=dict)
    #: per-precision multiplicative correction on predicted step time —
    #: the precision FEATURE of the cost model. Defaults assume the
    #: HBM-bound tiny-model regime (bf16 halves re-read bytes but not
    #: to 0.5x — dispatch and host shares don't scale; int8's dequant
    #: claws some back). Unlisted precisions (and f32) cost 1.0;
    #: recalibrate per backend like every other factor.
    precision_factors: Dict[str, float] = field(
        default_factory=lambda: dict(DEFAULT_PRECISION_FACTORS)
    )
    #: calibration provenance: sample counts per program
    samples: Dict[str, int] = field(default_factory=dict)
    #: the fitted learned-regressor section (PR 20), or None for a
    #: purely analytic/median-factor table — see
    #: :func:`validate_learned_section` for the schema. Inert unless
    #: ``GORDO_TPU_PERFMODEL`` is on.
    learned: Optional[dict] = None
    version: int = COST_TABLE_VERSION

    def precision_factor(self, precision: Optional[str]) -> float:
        return float(
            self.precision_factors.get(normalize_precision(precision), 1.0)
        )

    # -- learned-section evaluation -----------------------------------------

    def learned_entry(self, target: str, program: str) -> Optional[dict]:
        """The fitted model for ``(target, program)``, or None."""
        if not self.learned:
            return None
        return (self.learned.get("targets") or {}).get(target, {}).get(
            program
        )

    def learned_predict(
        self, target: str, program: str, features: Sequence[float]
    ) -> Optional[float]:
        """Evaluate the fitted log-linear model for ``(target,
        program)`` on a :func:`learned_feature_vector`: ``exp(intercept
        + coef·x)`` in the target's unit (ms or bytes). None when no
        model is fitted, the shape is out of the training domain, or the
        evaluation misbehaves — every None falls back analytic."""
        entry = self.learned_entry(target, program)
        if entry is None:
            return None
        try:
            lo, hi = entry["lo"], entry["hi"]
            for x, lo_i, hi_i in zip(features, lo, hi):
                if not (
                    lo_i - LEARNED_DOMAIN_SLACK
                    <= x
                    <= hi_i + LEARNED_DOMAIN_SLACK
                ):
                    return None
            coef = entry["coef"]
            z = float(coef[0]) + sum(
                float(c) * float(x) for c, x in zip(coef[1:], features)
            )
            value = math.exp(z)
        except (TypeError, ValueError, KeyError, IndexError, OverflowError):
            return None
        if not math.isfinite(value) or value < 0.0:
            return None
        return value

    def to_dict(self) -> dict:
        doc = {
            "version": self.version,
            "throughput": self.throughput,
            "compile_per_flop": self.compile_per_flop,
            "compile_floor_s": self.compile_floor_s,
            "dispatch_s": self.dispatch_s,
            "run_factors": dict(sorted(self.run_factors.items())),
            "compile_factors": dict(sorted(self.compile_factors.items())),
            "precision_factors": dict(sorted(self.precision_factors.items())),
            "samples": dict(sorted(self.samples.items())),
        }
        if self.learned is not None:
            doc["learned"] = self.learned
        return doc

    @classmethod
    def from_dict(cls, doc: dict) -> "CostTable":
        version = int(doc.get("version", 0))
        if version != COST_TABLE_VERSION:
            raise ValueError(
                f"cost table version {version} != supported "
                f"{COST_TABLE_VERSION}; re-run calibration"
            )
        return cls(
            throughput=float(doc.get("throughput", cls.throughput)),
            compile_per_flop=float(
                doc.get("compile_per_flop", cls.compile_per_flop)
            ),
            compile_floor_s=float(doc.get("compile_floor_s", cls.compile_floor_s)),
            dispatch_s=float(doc.get("dispatch_s", cls.dispatch_s)),
            run_factors={
                str(k): float(v) for k, v in (doc.get("run_factors") or {}).items()
            },
            compile_factors={
                str(k): float(v)
                for k, v in (doc.get("compile_factors") or {}).items()
            },
            # pre-precision tables (PR ≤13) carry no factor map: they
            # load with the analytic defaults rather than being rejected
            precision_factors={
                str(k): float(v)
                for k, v in (
                    doc.get("precision_factors") or DEFAULT_PRECISION_FACTORS
                ).items()
            },
            samples={
                str(k): int(v) for k, v in (doc.get("samples") or {}).items()
            },
            # a bad learned section degrades (warn + analytic), it never
            # rejects the table: the median factors are still good
            learned=validate_learned_section(doc.get("learned")),
            version=version,
        )

    def save(self, path: str) -> None:
        payload = json.dumps(self.to_dict(), indent=1, sort_keys=True) + "\n"
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "CostTable":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @property
    def calibrated(self) -> bool:
        return bool(self.run_factors or self.compile_factors)

    @property
    def has_learned(self) -> bool:
        return bool(
            self.learned and (self.learned.get("targets") or {})
        )


def load_table_safe(path: Optional[str]) -> CostTable:
    """A :class:`CostTable` from ``path`` that NEVER raises: a missing,
    truncated, torn or mis-versioned ``cost_table.json`` warns once and
    answers the analytic defaults — the contract the serve engine, the
    stream scorer and the lifecycle supervisor load through (a corrupt
    table must degrade predictions, not take down serving)."""
    if not path:
        return CostTable()
    try:
        return CostTable.load(path)
    except (OSError, ValueError, TypeError, KeyError) as exc:
        logger.warning(
            "Unusable cost table %s (%s); using the analytic defaults",
            path,
            exc,
        )
        return CostTable()


class CostModel:
    """Bucket-shape cost estimates against a :class:`CostTable`.

    ``mesh_shape`` is the trainer mesh's ``(model_axis, data_axis)`` —
    the estimator replicates the trainer's shape rounding so predicted
    program signatures (and therefore compile counts) match what XLA
    will actually see.
    """

    def __init__(
        self,
        table: Optional[CostTable] = None,
        mesh_shape: Tuple[int, int] = (1, 1),
        use_learned: Optional[bool] = None,
    ):
        self.table = table or CostTable()
        self.mesh_shape = (int(mesh_shape[0]), int(mesh_shape[1] or 1))
        #: learned-section participation, resolved ONCE at construction
        #: (``GORDO_TPU_PERFMODEL`` unless the caller pins it) so one
        #: model instance answers consistently for its whole lifetime —
        #: a plan costed half-analytic, half-learned would rank buckets
        #: against each other with two different rulers
        self.use_learned = (
            perfmodel_enabled() if use_learned is None else bool(use_learned)
        )

    def _learned(
        self,
        target: str,
        program: str,
        spec: ModelSpec,
        members: int,
        rows: int,
        epochs: int = 1,
        precision: Optional[str] = None,
    ) -> Optional[float]:
        """One knob-gated learned lookup; None means 'answer analytic'."""
        if not self.use_learned:
            return None
        return self.table.learned_predict(
            target,
            program,
            learned_feature_vector(
                spec_flops_per_sample(spec), members, rows, epochs, precision
            ),
        )

    # -- shape replication --------------------------------------------------

    def stacked_shape(
        self, m: int, n_padded: int, batch_size: int
    ) -> Tuple[int, int]:
        """``(m_total, n_total)`` after the trainer's mesh rounding
        (mirrors ``FleetTrainer._stack_bucket``): the model axis pads to
        a multiple of the mesh's model axis, the sample axis to a whole
        number of batches that also divides across the data axis."""
        model_axis, data_axis = self.mesh_shape
        m_total = -(-m // model_axis) * model_axis
        step = abs(batch_size * data_axis) // math.gcd(batch_size, data_axis)
        n_total = -(-n_padded // step) * step
        return m_total, n_total

    def stacked_windowed_shape(
        self, m: int, n_padded: int, offset: int, batch_size: int
    ) -> Tuple[int, int, int]:
        """``(m_total, series_rows, windows_total)`` after the trainer's
        windowed-stacker rounding (mirrors
        ``FleetTrainer._stack_windowed_bucket``): the series axis stays
        at ``n_padded`` exactly; only the virtual window axis mesh-rounds."""
        model_axis, data_axis = self.mesh_shape
        m_total = -(-m // model_axis) * model_axis
        step = abs(batch_size * data_axis) // math.gcd(batch_size, data_axis)
        nv_total = -(-(n_padded - offset) // step) * step
        return m_total, n_padded, nv_total

    # -- analytic estimates -------------------------------------------------

    def train_flops(
        self, spec: ModelSpec, m: int, n: int, epochs: int
    ) -> float:
        """Training FLOPs for ``m`` members × ``n`` (virtual) samples ×
        ``epochs`` epochs at this spec."""
        return (
            _TRAIN_FLOP_FACTOR
            * spec_flops_per_sample(spec)
            * float(m)
            * float(n)
            * float(max(epochs, 1))
        )

    def predict_run_s(
        self,
        program: str,
        spec: ModelSpec,
        m_total: int,
        n_total: int,
        epochs: int,
        precision: Optional[str] = None,
    ) -> float:
        """``precision`` is the program's compute precision (defaults to
        the spec's own ``compute_dtype``) — a feature of predicted step
        cost, corrected by the table's per-precision factor."""
        if precision is None:
            precision = compute_precision(spec)
        learned = self._learned(
            "device_ms", program, spec, m_total, n_total, epochs, precision
        )
        if learned is not None:
            return learned / 1000.0
        flops = self.train_flops(spec, m_total, n_total, epochs)
        factor = self.table.run_factors.get(program, 1.0)
        factor *= self.table.precision_factor(precision)
        return factor * (flops / self.table.throughput) + self.table.dispatch_s

    def predict_compile_s(self, program: str, spec: ModelSpec) -> float:
        # compile cost scales with program complexity, not data volume:
        # the learned model is keyed on the same static features with
        # the shape axes pinned to 1 (the fit side mirrors this)
        learned = self._learned("compile_ms", program, spec, 1, 1)
        if learned is not None:
            return learned / 1000.0
        factor = self.table.compile_factors.get(program, 1.0)
        return factor * (
            self.table.compile_floor_s
            + self.table.compile_per_flop * spec_flops_per_sample(spec)
        )

    def predict_hbm_bytes(
        self,
        spec: ModelSpec,
        m_total: int,
        n_total: int,
        batch_size: int,
        y_aliased: bool = True,
        series_rows: Optional[int] = None,
        precision: Optional[str] = None,
    ) -> int:
        """Resident device bytes of one bucket's training program:
        staged data + per-member params × optimizer copies + one batch
        of activations. ``series_rows`` switches to the windowed layout
        (series resident instead of materialized windows).

        ``precision`` (default: the spec's ``compute_dtype``) scales the
        ACTIVATION bytes — bf16 compute halves them, which changes how
        many members fit under the packer's HBM cap. Master params and
        staged f32 data keep full width during training (the models/nn
        mixed-precision contract: params never store reduced)."""
        if precision is None:
            precision = compute_precision(spec)
        learned = self._learned(
            "hbm_bytes",
            "fleet_windowed_fit" if series_rows is not None else "fleet_fit",
            spec,
            m_total,
            n_total,
            1,
            precision,
        )
        if learned is not None:
            return int(learned)
        f_in = getattr(spec, "n_features", 1)
        f_out = getattr(spec, "n_features_out", f_in)
        if series_rows is not None:
            data = m_total * series_rows * f_in + m_total * n_total * f_out
        else:
            data = m_total * n_total * f_in
            if not y_aliased:
                data += m_total * n_total * f_out
        data += 3 * m_total * n_total  # train/val weights + epoch bookkeeping
        params = spec_param_count(spec) * m_total * _OPTIMIZER_COPIES
        width = max(
            [f_in, f_out, *getattr(spec, "dims", ())] or [1]
        )
        lookback = getattr(spec, "lookback_window", 1)
        activations = m_total * batch_size * width * (
            len(getattr(spec, "dims", ())) + 2
        ) * lookback
        compute_bytes = PRECISION_COMPUTE_BYTES.get(
            normalize_precision(precision), 4
        )
        return int(4 * (data + params) + compute_bytes * activations)

    # -- serve-side estimates (the engine's precision ladder) ---------------

    def serve_weight_bytes(
        self, spec: ModelSpec, members: int, precision: str = "f32"
    ) -> int:
        """Resident weight bytes of one revision bucket at a serving
        precision: bf16 halves them, int8 quarters them (plus the
        per-channel f32 scales — one scale per output unit per member).
        This is the number the precision ladder exists to shrink: the
        HBM traffic every fused batch re-reads."""
        precision = normalize_precision(precision)
        weight_bytes = PRECISION_WEIGHT_BYTES.get(precision, 4)
        params = spec_param_count(spec) * members
        scales = 0
        if precision == "int8":
            dims = tuple(getattr(spec, "dims", ())) + (
                getattr(spec, "n_features_out", 1),
            )
            scales = 4 * members * sum(dims)  # f32 scale per out channel
        return int(weight_bytes * params + scales)

    def predict_serve_hbm_bytes(
        self, spec: ModelSpec, members: int, rows: int, precision: str = "f32"
    ) -> int:
        """Resident bytes of one fused serving batch: the precision's
        weight bucket + the staged payload at the compute width + the
        f32 output."""
        precision = normalize_precision(precision)
        learned = self._learned(
            "hbm_bytes", "fleet_forward", spec, members, rows, 1, precision
        )
        if learned is not None:
            return int(learned)
        f_in = getattr(spec, "n_features", 1)
        f_out = getattr(spec, "n_features_out", f_in)
        compute_bytes = PRECISION_COMPUTE_BYTES.get(precision, 4)
        payload = compute_bytes * members * rows * f_in
        output = 4 * members * rows * f_out  # always float32 out
        return self.serve_weight_bytes(spec, members, precision) + payload + output

    def predict_serve_step_s(
        self, spec: ModelSpec, members: int, rows: int, precision: str = "f32"
    ) -> float:
        """Predicted wall seconds of one fused serving batch (forward
        only — no train factor), with precision as a feature: the
        engine stamps this next to the measured device time on every
        batch span (predicted-vs-actual on the new axis)."""
        learned = self._learned(
            "device_ms", "fleet_forward", spec, members, rows, 1, precision
        )
        if learned is not None:
            return learned / 1000.0
        flops = spec_flops_per_sample(spec) * float(members) * float(rows)
        factor = self.table.run_factors.get("fleet_forward", 1.0)
        factor *= self.table.precision_factor(precision)
        return factor * (flops / self.table.throughput) + self.table.dispatch_s


def calibrate(
    trace_path: str, table: Optional[CostTable] = None
) -> CostTable:
    """
    Fit per-program correction factors from a ``build_trace.jsonl``.

    Reads every ``device_program`` span carrying the planner's static
    features (``params``/``flops_per_sample``/``members``/``epochs``,
    recorded by the trainer's program spans), splits them into compile
    (first call per signature) and run samples, and sets each program's
    factor to the MEDIAN of actual/analytic ratios — median, not mean,
    because a shared host's neighbor stalls put multi-second one-sided
    outliers into any wall-clock sample set.

    Returns a new :class:`CostTable`; the input ``table`` (default: the
    analytic defaults) provides the baseline constants the factors
    correct. Spans missing the static features (older traces) are
    skipped.
    """
    base = table or CostTable()
    model = CostModel(CostTable(  # factor-free baseline for the ratios
        throughput=base.throughput,
        compile_per_flop=base.compile_per_flop,
        compile_floor_s=base.compile_floor_s,
        dispatch_s=base.dispatch_s,
    ))
    run_ratios: Dict[str, list] = {}
    compile_ratios: Dict[str, list] = {}
    counts: Dict[str, int] = {}
    for span in _iter_spans(trace_path):
        if span.get("name") != "device_program":
            continue
        attrs = span.get("attributes") or {}
        program = str(attrs.get("program", ""))
        flops_per_sample = attrs.get("flops_per_sample")
        if not program or flops_per_sample is None:
            continue
        try:
            m = int(attrs.get("stacked_members") or attrs.get("members") or 0)
            n = int(attrs.get("stacked_samples") or 0)
            epochs = int(attrs.get("epochs") or 1)
            # prefer the device-measured time when the span carries one;
            # a span whose device_ms is present but zero/negative is a
            # broken sample and is SKIPPED — folding its wall-clock
            # duration into the median would let dispatch/queue noise
            # masquerade as device time
            device_ms = attrs.get("device_ms")
            if device_ms is not None:
                seconds = float(device_ms) / 1000.0
            else:
                seconds = float(span.get("duration_ms") or 0.0) / 1000.0
            flops_per_sample = float(flops_per_sample)
        except (TypeError, ValueError):
            continue
        if m <= 0 or n <= 0 or seconds <= 0.0:
            continue
        counts[program] = counts.get(program, 0) + 1
        flops = _TRAIN_FLOP_FACTOR * flops_per_sample * m * n * max(epochs, 1)
        analytic_run = flops / base.throughput + base.dispatch_s
        if attrs.get("compile"):
            analytic_compile = (
                base.compile_floor_s + base.compile_per_flop * flops_per_sample
            )
            # the first call is trace+compile+first run; subtract the
            # analytic run share so the factor corrects the compile part
            compile_ratios.setdefault(program, []).append(
                max(seconds - analytic_run, 1e-3) / analytic_compile
            )
        else:
            run_ratios.setdefault(program, []).append(seconds / analytic_run)

    def medians(ratios: Dict[str, list]) -> Dict[str, float]:
        out = {}
        for program, values in ratios.items():
            values = sorted(values)
            out[program] = round(values[len(values) // 2], 6)
        return out

    calibrated = CostTable(
        throughput=base.throughput,
        compile_per_flop=base.compile_per_flop,
        compile_floor_s=base.compile_floor_s,
        dispatch_s=base.dispatch_s,
        run_factors=medians(run_ratios),
        compile_factors=medians(compile_ratios),
        samples=counts,
    )
    logger.info(
        "Calibrated cost table from %s: %d program kind(s), %d span(s)",
        trace_path,
        len(counts),
        sum(counts.values()),
    )
    return calibrated


def _iter_spans(trace_path: str) -> Iterable[dict]:
    with open(trace_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue  # torn tail from a killed build
            if isinstance(doc, dict):
                yield doc
