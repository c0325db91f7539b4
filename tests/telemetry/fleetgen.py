"""
Synthetic fleet generator for the scale suite (``test_scale.py``).

Fabricates everything the observability plane holds for an N-machine
collection — member names, model specs, plan-packer member proxies, a
populated fleet-health ledger, serve-trace span sinks for the rollup
reducer — WITHOUT training a single model. The point is to exercise the
telemetry surfaces (build-plan, fleet-status, fleet-health, SLO
rollups, trace analysis, breaker board, prometheus scrape) at member
counts no real CI build could afford, so the scale thresholds (reshard
trigger, inline cap) are crossed, not assumed.

Determinism: everything is derived from the member index (names,
spec-family assignment, request/error counts, span ids/timestamps), so
two runs over the same N produce byte-identical corpora — the suite's
bytes-ratio and files-opened numbers are exact, not sampled.

Keep it stdlib + gordo_tpu only.
"""

import datetime
import json
import os
import types
from typing import Any, Dict, List, Optional, Tuple

#: a fixed, boring epoch (scale corpora must be reproducible; the
#: harness never reads the host clock for data)
EPOCH = 1_754_000_000.0


def machine_names(n: int, prefix: str = "scale-m") -> List[str]:
    """``scale-m-00000`` ... — zero-padded so sorted order == index
    order at any N."""
    width = max(5, len(str(max(n - 1, 0))))
    return [f"{prefix}-{i:0{width}d}" for i in range(n)]


def spec_families(count: int = 8):
    """A handful of distinct :class:`FeedForwardSpec` shapes — enough
    families that the packer has real bucketing work (members of one
    family share a fused program), few enough that 10k members still
    coalesce into a bounded program set, like a real fleet."""
    from gordo_tpu.models.spec import FeedForwardSpec

    families = []
    for i in range(count):
        width = 16 * (1 + i % 4)
        features = 8 + 2 * (i % 3)
        families.append(
            FeedForwardSpec(
                n_features=features,
                n_features_out=features,
                dims=(width, width // 2, width),
                activations=("tanh", "tanh", "tanh"),
            )
        )
    return families


def plan_members(
    n: int, families: int = 8
) -> List[types.SimpleNamespace]:
    """Shape-only plan-packer member proxies (the
    ``FleetBuilder._plan_member_proxy`` dense shape: name / spec /
    sample count / X-y aliasing tokens) — what ``plan_train_buckets``
    reads, with no arrays behind them."""
    specs = spec_families(families)
    members = []
    for i, name in enumerate(machine_names(n)):
        token = object()
        members.append(
            types.SimpleNamespace(
                name=name,
                spec=specs[i % len(specs)],
                n=2000 + 128 * (i % 7),
                X=token,
                y=token,
            )
        )
    return members


def build_fleet_plan(n: int, families: int = 8):
    """The full build-plan artifact for an N-member synthetic fleet —
    the packer + plan-doc assembly path the builder's ``bucket_plan``
    phase runs, minus the data loading around it."""
    from gordo_tpu import planner
    from gordo_tpu.models.training import FitConfig

    config = FitConfig(epochs=5, batch_size=32)
    cost_model = planner.CostModel()
    strategy = planner.default_strategy()
    members = plan_members(n, families=families)
    buckets = planner.plan_train_buckets(
        members, config, strategy=strategy, cost_model=cost_model
    )
    fingerprint = planner.config_fingerprint(
        [f"scale-{i:08x}" for i in range(min(n, 512))]
    )
    return planner.build_plan_doc(
        [(config, buckets)],
        strategy,
        cost_model.mesh_shape,
        cost_model.table,
        fingerprint,
    )


def populate_ledger(ledger, names: List[str]) -> None:
    """Feed an N-machine fleet's worth of health records through the
    ledger's real mutator paths (requests, scored rows, build
    provenance, drift verdicts, a sprinkling of quarantines) — the
    state mix fleet-status and the offender ranking must digest. All
    batched (``write=False``) with one flush, like the lifecycle loop."""
    for i, name in enumerate(names):
        ledger.record_scores(
            name,
            rows=100 + i % 50,
            residual_mean=0.01 + 0.001 * (i % 10),
            write=False,
        )
        ledger.record_build(name, revision="1754000000000", final_loss=0.02)
        if i % 251 == 0:
            ledger.record_build(
                name, failed=True, error="synthetic build fault"
            )
        if i % 97 == 0:
            ledger.record_drift(
                name,
                True,
                reasons=["residual_ratio 2.1x"],
                stats={"residual_ratio": 2.1},
                write=False,
            )
    quarantined = [name for i, name in enumerate(names) if i % 503 == 0]
    if quarantined:
        ledger.record_quarantine(
            quarantined,
            revision="1754000000000",
            reasons=["gate error_rate"],
        )
    ledger.flush()


def _iso(ts: float) -> str:
    return datetime.datetime.fromtimestamp(
        ts, datetime.timezone.utc
    ).isoformat()


def request_span(
    i: int,
    ts: float,
    machine: str,
    status: int = 200,
    wall_ms: float = 80.0,
) -> Dict[str, Any]:
    """One serve-trace ``request`` span in the recorder's wire shape."""
    return {
        "name": "request",
        "context": {
            "trace_id": f"{i:032x}",
            "span_id": f"{i:016x}",
        },
        "parent_id": None,
        "kind": "server",
        "start_time": _iso(ts - wall_ms / 1000.0),
        "end_time": _iso(ts),
        "duration_ms": wall_ms,
        "status": {"status_code": "OK" if status < 500 else "ERROR"},
        "attributes": {"http.status_code": status, "gordo_name": machine},
        "resource": {"service.name": "bench-scale"},
    }


def write_span_corpus(
    directory: str,
    n_spans: int,
    machines: List[str],
    windows: int = 16,
    window_seconds: int = 60,
    base_name: str = "serve_trace.jsonl",
    start: float = EPOCH,
) -> Tuple[str, float, float]:
    """A serve-trace sink spreading ``n_spans`` requests evenly over
    ``windows`` rollup windows; returns (path, first_ts, last_ts)."""
    path = os.path.join(directory, base_name)
    span_gap = (windows * window_seconds) / max(1, n_spans)
    first = last = start
    with open(path, "w") as handle:
        for i in range(n_spans):
            ts = start + i * span_gap
            last = ts
            machine = machines[i % len(machines)] if machines else "m-0"
            status = 500 if i % 211 == 0 else 200
            handle.write(
                json.dumps(request_span(i, ts, machine, status=status))
            )
            handle.write("\n")
    return path, first, last


def make_breaker_board(n: int, tripped: int = 8):
    """A breaker board tracking ``n`` members of one live fleet, with
    ``tripped`` of them tripped OPEN — the shape a bounded summary must
    stay cheap on."""
    from gordo_tpu.serve.breaker import BreakerBoard, BreakerConfig

    board = BreakerBoard(config=BreakerConfig(threshold=1))

    class _Fleet:  # weakref-able stand-in for a RevisionFleet
        pass

    fleet = _Fleet()
    board._fleet_anchor = fleet  # keep the fleet alive with the board
    spec = "spec-0"
    names = machine_names(n)
    with board._lock:
        fid = board._track_fleet(fleet)
        from gordo_tpu.serve.breaker import _MemberBreaker

        for name in names:
            board._members[(fid, spec, name)] = _MemberBreaker(name)
    for name in names[:tripped]:
        board.record_failure(fleet, spec, name, RuntimeError("synthetic"))
    return board
