"""
Bucket construction as bin packing.

The trainer's original grouping is syntactic — exact ``(spec,
round_up_pow2(n))`` keys — which fragments heterogeneous fleets into
many compiles and discovers over-packed buckets only reactively (the
device-error bisection ladder). This module makes bucket composition an
explicit optimization with three levers:

- **shape ladders** (:mod:`~gordo_tpu.planner.ladder`): the sample axis
  quantizes up a geometric ladder (default ratio 1.25 — pow2's worst
  case wastes ~2x FLOPs per axis) shared with the serving engine;
- **HBM caps**: members best-fit-decreasing into buckets whose predicted
  resident bytes stay under a cap, splitting *before* the OOM the
  bisection ladder would otherwise pay for (staging + compile + the
  failed run, twice per halving);
- **a compile budget**: every distinct stacked shape mints one XLA
  program, so rungs merge upward (cheapest padding-waste increase
  first) until the planned program count fits the budget — the explicit
  trade between padding waste and compile count. Buckets split under
  the HBM cap additionally pad their member axis to a shared pow2 rung,
  so k same-rung buckets cost one compile, not k.

Strategies: ``naive`` keeps the trainer's historical exact-key grouping
— dense members still pad pow2 bit-for-bit; windowed members now pad
their series axis up the geometric ladder (the deliberate time-axis
fix, so existing LSTM fleets DO get new padded shapes on the default
path) — ``packed`` is the cost-optimized packer. Both are deterministic
in member order.

Dependency note: members are duck-typed (``.name``/``.spec``/``.n`` or
``.series``/``.n_windows``) — this module must not import
``gordo_tpu.parallel`` (the trainer imports *us*).
"""

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..models.spec import ModelSpec
from ..utils.env import env_int, env_str
from .costmodel import CostModel, spec_state_bytes
from .ladder import round_up_ladder, sample_pad_ratio, series_pad_ratio

logger = logging.getLogger(__name__)

NAIVE = "naive"
PACKED = "packed"
STRATEGIES = (NAIVE, PACKED)

STRATEGY_ENV = "GORDO_TPU_PLAN_STRATEGY"
COMPILE_BUDGET_ENV = "GORDO_TPU_PLAN_COMPILE_BUDGET"
HBM_CAP_ENV = "GORDO_TPU_PLAN_HBM_CAP_BYTES"

#: default per-bucket resident-bytes cap for the packed strategy — the
#: build-path analog of GORDO_TPU_CV_CHUNK_BYTES, applied to the cost
#: model's predicted footprint (data + optimizer copies + activations),
#: not just raw staged bytes
DEFAULT_HBM_CAP_BYTES = 4 << 30


def default_strategy() -> str:
    """The build-wide strategy (``GORDO_TPU_PLAN_STRATEGY``; default
    ``naive`` — the historical grouping stays the default until a plan
    or an explicit flag opts a build in)."""
    raw = (env_str(STRATEGY_ENV, NAIVE) or NAIVE).strip().lower()
    if raw not in STRATEGIES:
        logger.warning("Invalid %s=%r; using %r", STRATEGY_ENV, raw, NAIVE)
        return NAIVE
    return raw


def compile_budget() -> int:
    """Hard program-count cap for the packed strategy
    (``GORDO_TPU_PLAN_COMPILE_BUDGET``; 0 = no cap, rung merging stops
    at the cost model's compile-vs-padding break-even instead)."""
    return max(0, env_int(COMPILE_BUDGET_ENV, 0))


def hbm_cap_bytes() -> int:
    return max(1 << 20, env_int(HBM_CAP_ENV, DEFAULT_HBM_CAP_BYTES))


def trains_alone(spec: ModelSpec) -> bool:
    """True for a member whose training state alone (weights, gradients,
    optimizer moments) exceeds the per-bucket cap, or whose spec has no
    member axis (``ModelSpec.member_axis``): no second member can share
    its program, under any strategy. Such members train, and their folds
    score, one to a program; programs of one shape share one compile."""
    return not spec.member_axis or spec_state_bytes(spec) > hbm_cap_bytes()


#: windows a scan step of a windowed scoring program forwards (CV fold
#: scores, the fleet route): bounds the program's memory like a fit batch
WINDOWED_SCORING_BATCH = 256
#: the same for a member that trains alone: 256 of its windows are a fit
#: step's activations eight times over, so it scores at a fit's batch
ALONE_SCORING_BATCH = 32
#: ... and no more rows a step than 32 windows of 512 hold: a step's
#: activations follow its rows, and a window of 8,192 is sixteen of those
ALONE_SCORING_ROWS = 32 * 512


def windowed_scoring_batch(spec: ModelSpec) -> int:
    if not trains_alone(spec):
        return WINDOWED_SCORING_BATCH
    return max(1, min(ALONE_SCORING_BATCH, ALONE_SCORING_ROWS // spec.lookback_window))


def _round_up_pow2(n: int, batch_size: int) -> int:
    """The trainer's historical pad target: next power of two, at least
    one full batch (kept in sync with ``parallel/fleet.py`` via the
    naive-parity test)."""
    target = max(n, batch_size)
    power = 1
    while power < target:
        power <<= 1
    return ((power + batch_size - 1) // batch_size) * batch_size


def member_is_windowed(member: Any) -> bool:
    return hasattr(member, "series")


def member_samples(member: Any) -> int:
    """The member's (virtual) sample count on the padded axis."""
    return len(member.series) if member_is_windowed(member) else member.n


def naive_pad_target(member: Any, batch_size: int) -> int:
    """The naive strategy's pad target for one member — pow2 on the
    dense sample axis, the geometric series ladder on the windowed time
    axis (the pow2 time-axis padding was the measured ~2x waste case)."""
    if member_is_windowed(member):
        return round_up_ladder(len(member.series), series_pad_ratio())
    return _round_up_pow2(member.n, batch_size)


def member_offset(member: Any) -> int:
    if member_is_windowed(member):
        return len(member.series) - member.n_windows
    return 0


def _spec_program(member: Any) -> str:
    return "fleet_windowed_fit" if member_is_windowed(member) else "fleet_fit"


def _member_bytes(cost_model: CostModel, member: Any, n_padded: int, batch: int) -> int:
    """One member's marginal predicted footprint inside a bucket padded
    to ``n_padded`` (the bin-packing item weight)."""
    if member_is_windowed(member):
        return cost_model.predict_hbm_bytes(
            member.spec,
            1,
            n_padded - member_offset(member),
            batch,
            series_rows=n_padded,
        )
    y_aliased = getattr(member, "y", None) is getattr(member, "X", None)
    return cost_model.predict_hbm_bytes(
        member.spec, 1, n_padded, batch, y_aliased=y_aliased
    )


@dataclass
class PlannedBucket:
    """One training bucket the trainer will run as one device program.

    ``n_padded`` is the pre-mesh-rounding sample-axis pad target (the
    bucket key the trainer historically carried); ``m_padded`` an
    optional member-axis pad target (dummy zero-weight members up to a
    shared rung so sibling buckets reuse one compile); ``predicted``
    the cost model's estimates for the *padded* program.
    """

    bucket_id: str
    program: str
    spec: ModelSpec
    members: List[Any]
    n_padded: int
    m_padded: Optional[int] = None
    offset: int = 0
    windowed: bool = False
    predicted: Dict[str, Any] = field(default_factory=dict)

    @property
    def member_names(self) -> List[str]:
        return [m.name for m in self.members]


def _bucket_key(spec: ModelSpec, config: Any) -> str:
    """Deterministic (cross-process) short id for a (spec geometry, fit
    config) pair. The config MUST participate: a FleetPlan holds buckets
    from every fit-config group, and two groups sharing a spec and rung
    would otherwise collide on id — ``materialize_buckets`` keys member
    rosters by id, so a collision trains the pooled members twice."""
    import hashlib

    fit = (
        getattr(config, "epochs", None),
        getattr(config, "batch_size", None),
        getattr(config, "validation_split", None),
        getattr(config, "shuffle", None),
        tuple(getattr(config, "early_stopping", None) or ()) or None,
    )
    return hashlib.sha256(f"{spec!r}|{fit!r}".encode()).hexdigest()[:10]


# -- strategies ---------------------------------------------------------------


def _naive_buckets(members: Sequence[Any], config: Any) -> List[PlannedBucket]:
    """The historical grouping: one bucket per exact
    ``(spec, pad_target[, offset])`` key, members in input order."""
    grouped: Dict[Tuple, List[Any]] = {}
    for member in members:
        key = (
            member.spec,
            naive_pad_target(member, config.batch_size),
            member_offset(member),
            member_is_windowed(member),
        )
        grouped.setdefault(key, []).append(member)
    buckets = []
    for (spec, n_padded, offset, windowed), group in grouped.items():
        # a member larger than the cap trains alone: one-member buckets
        # of one shape, so one compile runs once a member
        rosters = [[m] for m in group] if trains_alone(spec) else [group]
        for idx, bucket_members in enumerate(rosters):
            buckets.append(
                PlannedBucket(
                    bucket_id=f"{_bucket_key(spec, config)}-n{n_padded}"
                    + (f"-o{offset}" if windowed else "")
                    + (f"-b{idx}" if len(rosters) > 1 else ""),
                    program=_spec_program(bucket_members[0]),
                    spec=spec,
                    members=bucket_members,
                    n_padded=n_padded,
                    offset=offset,
                    windowed=windowed,
                )
            )
    return buckets


def _packed_buckets(
    members: Sequence[Any],
    config: Any,
    cost_model: CostModel,
    budget: Optional[int] = None,
    hbm_cap: Optional[int] = None,
) -> List[PlannedBucket]:
    budget = compile_budget() if budget is None else budget
    hbm_cap = hbm_cap_bytes() if hbm_cap is None else hbm_cap
    batch = config.batch_size
    input_pos = {m.name: i for i, m in enumerate(members)}

    # 1. quantize each member up the geometric ladder
    rung_groups: Dict[Tuple, List[Any]] = {}
    for member in members:
        if member_is_windowed(member):
            rung = round_up_ladder(len(member.series), series_pad_ratio())
        else:
            rung = round_up_ladder(
                max(member.n, batch), sample_pad_ratio(), multiple=batch
            )
        key = (
            member.spec,
            member_offset(member),
            member_is_windowed(member),
            rung,
        )
        rung_groups.setdefault(key, []).append(member)

    # 2. the compile-vs-padding trade: merging a rung into the next one
    #    up (within one (spec, offset) family — shapes across specs can
    #    never merge) removes one compiled program at the price of extra
    #    padded samples for the merged members. Merge while the cost
    #    model says the compile saved outweighs the run time added
    #    (cheapest merge first); with an explicit ``budget``, keep
    #    merging past break-even until the program count fits.
    def _candidate_merges():
        families: Dict[Tuple, List[Tuple]] = {}
        for key in rung_groups:
            families.setdefault(key[:3], []).append(key)
        merges = []  # (added_run_s, compile_saved_s, src_key, dst_key)
        for family_keys in families.values():
            family_keys.sort(key=lambda k: k[3])
            for src, dst in zip(family_keys[:-1], family_keys[1:]):
                spec, _, windowed, _ = src
                program = "fleet_windowed_fit" if windowed else "fleet_fit"
                added_flops = (
                    (dst[3] - src[3])
                    * len(rung_groups[src])
                    * cost_model.train_flops(spec, 1, 1, config.epochs)
                )
                added_run_s = (
                    cost_model.table.run_factors.get(program, 1.0)
                    * added_flops
                    / cost_model.table.throughput
                )
                compile_saved_s = cost_model.predict_compile_s(program, spec)
                merges.append((added_run_s, compile_saved_s, src, dst))
        return merges

    while len(rung_groups) > 1:
        merges = _candidate_merges()
        if not merges:
            break
        if budget and len(rung_groups) > budget:
            # forced past break-even: take the cheapest padding increase
            # (index tiebreak keeps ties deterministic — spec keys are
            # not orderable)
            pick = min(
                range(len(merges)), key=lambda i: (merges[i][0], i)
            )
        else:
            # voluntary: take the largest net win across ALL families —
            # a family whose cheapest-padding merge is unprofitable must
            # not mask a profitable merge elsewhere
            pick = max(
                range(len(merges)),
                key=lambda i: (merges[i][1] - merges[i][0], -i),
            )
            added_run_s, compile_saved_s = merges[pick][:2]
            if added_run_s >= compile_saved_s:
                break  # padding now costs more than any compile it saves
        _, _, src, dst = merges[pick]
        rung_groups[dst] = rung_groups[dst] + rung_groups.pop(src)

    # 3. HBM cap: best-fit-decreasing inside each rung group, splitting
    #    BEFORE the program would out-size device memory.
    buckets: List[PlannedBucket] = []
    for (spec, offset, windowed, rung), group in rung_groups.items():
        # rung merges append groups out of input order; restore it so
        # bucket rosters (and the plan JSON) are input-order stable
        group = sorted(group, key=lambda m: input_pos[m.name])
        weights = {
            m.name: _member_bytes(cost_model, m, rung, batch) for m in group
        }
        order = sorted(
            range(len(group)), key=lambda i: (-weights[group[i].name], i)
        )
        bins: List[Tuple[List[Any], int]] = []  # (members, used_bytes)
        # a member that trains alone takes a bin of its own whatever it
        # weighs: its spec may have no member axis to share a program on
        alone = trains_alone(spec)
        for i in order:
            member = group[i]
            size = weights[member.name]
            best_bin = None
            for b, (bin_members, used) in enumerate([] if alone else bins):
                if used + size <= hbm_cap:
                    if best_bin is None or used > bins[best_bin][1]:
                        best_bin = b
            if best_bin is None:
                bins.append(([member], size))
            else:
                bin_members, used = bins[best_bin]
                bin_members.append(member)
                bins[best_bin] = (bin_members, used + size)
        # restore input order inside each bin (fold-major contracts and
        # deterministic artifacts both key off member order)
        packed_bins = [
            sorted(bin_members, key=lambda m: input_pos[m.name])
            for bin_members, _ in bins
        ]
        # sibling bins share one compile by padding their member axis to
        # a common pow2 rung (dummies are zero-weight vmap rows — per-
        # member numerics are unaffected, see parallel/fleet.py RNG note)
        m_padded = None
        if len(packed_bins) > 1:
            m_padded = round_up_ladder(max(len(b) for b in packed_bins), 2.0)
        for idx, bin_members in enumerate(packed_bins):
            buckets.append(
                PlannedBucket(
                    bucket_id=f"{_bucket_key(spec, config)}-n{rung}"
                    + (f"-o{offset}" if windowed else "")
                    + (f"-b{idx}" if len(packed_bins) > 1 else ""),
                    program=_spec_program(bin_members[0]),
                    spec=spec,
                    members=bin_members,
                    n_padded=rung,
                    m_padded=m_padded,
                    offset=offset,
                    windowed=windowed,
                )
            )
    return buckets


def annotate_predictions(
    buckets: Sequence[PlannedBucket], config: Any, cost_model: CostModel
) -> None:
    """Fill each bucket's ``predicted`` dict (run/compile seconds, HBM
    bytes, padded-FLOP waste, stacked shape) and attribute each distinct
    stacked signature's compile to its FIRST bucket — later buckets of
    the same signature hit the jit cache, exactly like the telemetry's
    first-call-per-signature attribution."""
    seen_signatures = set()
    for bucket in buckets:
        m = max(len(bucket.members), bucket.m_padded or 0)
        if bucket.windowed:
            # the trainer's windowed stacker keeps the series axis at
            # n_padded exactly and mesh-rounds only the window axis
            m_total, n_series, n_total = cost_model.stacked_windowed_shape(
                m, bucket.n_padded, bucket.offset, config.batch_size
            )
            shape = [m_total, n_series, n_total]
        else:
            m_total, n_total = cost_model.stacked_shape(
                m, bucket.n_padded, config.batch_size
            )
            shape = [m_total, n_total]
        signature = (repr(bucket.spec), bucket.program, tuple(shape))
        compiles = 0 if signature in seen_signatures else 1
        seen_signatures.add(signature)
        true_flops = sum(
            cost_model.train_flops(
                bucket.spec,
                1,
                member_samples(member) - (bucket.offset if bucket.windowed else 0),
                config.epochs,
            )
            for member in bucket.members
        )
        padded_flops = cost_model.train_flops(
            bucket.spec, m_total, n_total, config.epochs
        )
        run_s = cost_model.predict_run_s(
            bucket.program, bucket.spec, m_total, n_total, config.epochs
        )
        compile_s = (
            cost_model.predict_compile_s(bucket.program, bucket.spec)
            if compiles
            else 0.0
        )
        if bucket.windowed:
            hbm = cost_model.predict_hbm_bytes(
                bucket.spec,
                m_total,
                n_total,
                config.batch_size,
                series_rows=bucket.n_padded,
            )
        else:
            aliased = all(
                getattr(mm, "y", None) is getattr(mm, "X", None)
                for mm in bucket.members
            )
            hbm = cost_model.predict_hbm_bytes(
                bucket.spec, m_total, n_total, config.batch_size, y_aliased=aliased
            )
        bucket.predicted = {
            "members": len(bucket.members),
            "stacked_shape": shape,
            "compiles": compiles,
            "compile_s": round(compile_s, 6),
            "run_s": round(run_s, 6),
            "hbm_bytes": int(hbm),
            "flops_true": float(f"{true_flops:.6g}"),
            "flops_padded": float(f"{padded_flops:.6g}"),
            "padding_waste": round(
                1.0 - true_flops / padded_flops if padded_flops else 0.0, 6
            ),
        }


def plan_train_buckets(
    members: Sequence[Any],
    config: Any,
    strategy: Optional[str] = None,
    cost_model: Optional[CostModel] = None,
    plan: Optional[Any] = None,
    budget: Optional[int] = None,
    hbm_cap: Optional[int] = None,
) -> List[PlannedBucket]:
    """
    Group ``members`` (a mix of dense and windowed fleet members) into
    training buckets.

    With a :class:`~gordo_tpu.planner.plan.FleetPlan`, members the plan
    covers keep their planned bucket composition and pad targets
    (numerics-stable across ``--resume``: a member's padded shape never
    changes because its neighbors finished); uncovered members — CV fold
    members, late additions — pack live with ``strategy``.
    """
    if not members:
        return []
    strategy = strategy or default_strategy()
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown plan strategy {strategy!r}")
    cost_model = cost_model or CostModel()

    planned: List[PlannedBucket] = []
    remaining = list(members)
    if plan is not None:
        planned, remaining = plan.materialize_buckets(members)
    if remaining:
        if strategy == PACKED:
            planned += _packed_buckets(
                remaining, config, cost_model, budget=budget, hbm_cap=hbm_cap
            )
        else:
            planned += _naive_buckets(remaining, config)
    annotate_predictions(planned, config, cost_model)
    return planned
