"""
Telemetry-overhead microbench: the same small CPU fleet build with
telemetry off vs on, so overhead regressions in the span recorder /
heartbeat path show up in the bench trajectory.

Writes ``BENCH_TELEMETRY.json`` at the repo root (the committed bench
convention, like BASELINE.json). The acceptance bar for
the observability layer is telemetry-on within 3% of telemetry-off
wall-clock; the recorder's per-span cost is a few microseconds and the
heartbeat a few hundred bytes per machine, so the realized overhead on
even this 8-machine toy build sits in the noise floor.

Run:  JAX_PLATFORMS=cpu python benchmarks/bench_telemetry.py
(or ``make bench-telemetry``). Not run in CI, like the rest of
benchmarks/ — but ``tests/telemetry`` asserts the mechanism and this
script's harness stays importable.
"""

import datetime
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: big enough that one build runs seconds, not hundreds of ms — shared
#: CI hosts show ±50% wall-clock noise on sub-second work, which would
#: swamp the ~tens-of-ms fixed telemetry cost this bench exists to
#: bound. The heartbeat throttle makes the telemetry cost near-constant
#: in machine count, so a bigger fleet measures the honest production
#: overhead fraction, not a toy-amplified one.
N_MACHINES = 32
N_EPOCHS = 10
#: floors converge as both modes sample quiet windows; on a busy shared
#: host fewer than ~10 reps risks only one mode hitting one
REPS = 11

DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-05T00:00:00+00:00",
    "tag_list": ["t1", "t2", "t3"],
}

MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.models.JaxAutoEncoder": {
                "kind": "feedforward_hourglass",
                "encoding_layers": 1,
                "epochs": N_EPOCHS,
            }
        }
    }
}


def make_machines():
    from gordo_tpu.machine import Machine

    return [
        Machine.from_config(
            {"name": f"bench-{i}", "model": MODEL, "dataset": dict(DATASET)},
            project_name="bench-telemetry",
        )
        for i in range(N_MACHINES)
    ]


def one_build(telemetry_on: bool) -> float:
    """One fleet build into a throwaway dir; returns wall seconds."""
    from gordo_tpu.parallel import FleetBuilder

    os.environ["GORDO_TPU_TELEMETRY"] = "1" if telemetry_on else "0"
    out = tempfile.mkdtemp(prefix="bench-telemetry-")
    try:
        start = time.perf_counter()
        builder = FleetBuilder(make_machines())
        results = builder.build(output_dir=out)
        elapsed = time.perf_counter() - start
        assert len(results) == N_MACHINES, builder.build_errors
        return elapsed
    finally:
        shutil.rmtree(out, ignore_errors=True)


def main() -> dict:
    # Warmup: compile every program once so both measured modes run the
    # same steady-state cache-hit path (compile time would otherwise
    # land entirely on whichever mode runs first).
    one_build(telemetry_on=False)
    one_build(telemetry_on=True)

    # Shared CI hosts show ±50% wall-clock noise on identical work over
    # tens of seconds (neighbor stalls of multiple seconds were
    # measured), which swamps any mean/median aggregate. The stable
    # comparison is the QUIET-WINDOW FLOOR: interleave the modes (order
    # alternating to cancel drift) so both sample quiet windows, then
    # compare per-mode minima — the only estimator whose noise is
    # one-sided. Pair ratios are reported alongside for context.
    import statistics

    runs = {"telemetry_off": [], "telemetry_on": []}
    pair_pcts = []
    for rep in range(REPS):
        if rep % 2 == 0:
            off_sec = one_build(telemetry_on=False)
            on_sec = one_build(telemetry_on=True)
        else:
            on_sec = one_build(telemetry_on=True)
            off_sec = one_build(telemetry_on=False)
        runs["telemetry_off"].append(off_sec)
        runs["telemetry_on"].append(on_sec)
        pair_pcts.append((on_sec - off_sec) / off_sec * 100.0)

    timings = {
        mode: {
            "runs_sec": values,
            "best_sec": min(values),
            "median_sec": statistics.median(values),
        }
        for mode, values in runs.items()
    }
    off = timings["telemetry_off"]["best_sec"]
    on = timings["telemetry_on"]["best_sec"]
    overhead_pct = (on - off) / off * 100.0
    doc = {
        "bench": "telemetry-overhead",
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "machines": N_MACHINES,
        "epochs": N_EPOCHS,
        "reps": REPS,
        "telemetry_off_sec": round(off, 4),
        "telemetry_on_sec": round(on, 4),
        "pair_overhead_pcts": [round(p, 2) for p in pair_pcts],
        "median_pair_overhead_pct": round(statistics.median(pair_pcts), 2),
        "overhead_pct": round(overhead_pct, 2),
        "within_3pct": overhead_pct <= 3.0,
        "runs": timings,
    }
    out_path = REPO_ROOT / "BENCH_TELEMETRY.json"
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(doc, indent=1, sort_keys=True))
    print(f"\nwrote {out_path}")
    return doc


if __name__ == "__main__":
    main()
