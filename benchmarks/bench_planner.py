"""
Bucket-planner benchmark: a heterogeneous synthetic fleet trained with
the ``naive`` (historical pow2 exact-key grouping) vs ``packed``
(cost-model bin packing) strategies.

The fleet is built to look like a real heterogeneous site: one spec
family with sample counts scattered across pow2 boundaries (naive
fragments it into four compiles; packed merges the rungs), one family
clustered just above a pow2 boundary (naive pads every member ~2x;
packed's 1.25 ladder caps the waste), and one family whose members land
on rungs both ladders share (so per-member numerics must be IDENTICAL
across strategies — the no-divergence acceptance bar).

Each (strategy, rep) runs in a fresh subprocess so XLA compiles are
paid honestly, the FleetPlan is computed in-process, and the telemetry
trace (``build_trace.jsonl``) supplies the actual compile count the
plan's prediction is checked against. This parent imports no JAX and
pins every child to the CPU platform, one child at a time: the bench
counts compiles and never takes a chip.

Writes ``BENCH_PLAN.json`` at the repo root (the committed bench
convention). Run: ``JAX_PLATFORMS=cpu python benchmarks/bench_planner.py``
or ``make bench-planner``. Not run in CI; ``tests/planner`` asserts the
mechanisms and this harness stays importable.
"""

import datetime
import json
import os
import statistics
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: compile cost dominates this bench (the point); a handful of reps is
#: enough for a stable best-of on a shared host
REPS = 5
EPOCHS = 2
BATCH = 16

#: the heterogeneous fleet: (family, n_features, dims, sample counts)
FLEET = [
    # scattered across pow2 boundaries -> naive mints 4 programs
    ("scatter", 3, (6, 3), [70, 100, 140, 200, 260, 380, 520, 640]),
    # clustered just above 1024 -> naive pads all 8 members to 2048
    ("cluster", 4, (8, 4), [1040, 1070, 1100, 1160, 1200, 1240, 1280, 1340]),
    # on rungs both ladders share (and one merge inside the shared rung)
    # -> identical bucket composition and padding under both strategies
    ("parity", 5, (10, 5), [100, 128]),
]

WORKER = textwrap.dedent(
    """
    import json
    import os
    import sys
    import time

    sys.path.insert(0, {repo_root!r})
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from gordo_tpu import telemetry
    from gordo_tpu.models.factories import feedforward_symmetric
    from gordo_tpu.models.training import FitConfig
    from gordo_tpu.parallel import FleetMember, FleetTrainer
    from gordo_tpu import planner

    strategy = {strategy!r}
    fleet = {fleet!r}
    out_dir = {out_dir!r}

    config = FitConfig(epochs={epochs}, batch_size={batch}, shuffle=False)

    members = []
    for fam_idx, (family, n_features, dims, counts) in enumerate(fleet):
        spec = feedforward_symmetric(
            n_features, dims=tuple(dims), funcs=("tanh",) * len(dims)
        )
        for idx, n in enumerate(counts):
            rng = np.random.RandomState(1000 * fam_idx + idx)
            X = rng.rand(n, n_features).astype(np.float32)
            members.append(
                FleetMember(
                    name=f"{{family}}-{{idx}}",
                    spec=spec,
                    X=X,
                    y=X.copy(),
                    seed=idx,
                )
            )

    trainer = FleetTrainer(plan_strategy=strategy)
    cost_model = trainer.cost_model()
    buckets = planner.plan_train_buckets(
        members, config, strategy=strategy, cost_model=cost_model
    )
    plan = planner.build_plan_doc(
        [(config, buckets)],
        strategy,
        cost_model.mesh_shape,
        cost_model.table,
        planner.config_fingerprint([m.name for m in members]),
    )

    trace_path = os.path.join(out_dir, "build_trace.jsonl")
    recorder = telemetry.SpanRecorder(
        sink_path=trace_path, service="bench-planner"
    )
    with telemetry.activate(recorder):
        start = time.perf_counter()
        results = trainer.train(members, config)
        wall = time.perf_counter() - start
    recorder.close()

    compiles = 0
    fit_seconds = 0.0
    with open(trace_path) as f:
        for line in f:
            span = json.loads(line)
            if span.get("name") != "device_program":
                continue
            attrs = span["attributes"]
            if not attrs["program"].endswith("_fit"):
                continue
            fit_seconds += span["duration_ms"] / 1000.0
            if attrs["compile"]:
                compiles += 1

    print(
        "BENCH_RESULT "
        + json.dumps(
            {{
                "strategy": strategy,
                "wall_sec": wall,
                "fit_sec": fit_seconds,
                "compiles_actual": compiles,
                "compiles_predicted": plan.totals["compiles"],
                "buckets": plan.totals["buckets"],
                "padding_waste": plan.totals["padding_waste"],
                "flops_true": plan.totals["flops_true"],
                "flops_padded": plan.totals["flops_padded"],
                "plan_hash": plan.plan_hash,
                "losses": {{
                    r.name: float(r.history.history["loss"][-1])
                    for r in results
                }},
            }}
        )
    )
    """
)


def run_once(strategy: str) -> dict:
    with tempfile.TemporaryDirectory() as out_dir:
        script = WORKER.format(
            repo_root=str(REPO_ROOT),
            strategy=strategy,
            fleet=FLEET,
            out_dir=out_dir,
            epochs=EPOCHS,
            batch=BATCH,
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # 1-device CPU: no member-axis padding
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{strategy} bench run failed:\n{proc.stderr[-4000:]}"
            )
        line = next(
            l
            for l in proc.stdout.splitlines()
            if l.startswith("BENCH_RESULT ")
        )
        return json.loads(line.split(" ", 1)[1])


def main() -> int:
    runs = {"naive": [], "packed": []}
    for rep in range(REPS):
        for strategy in ("naive", "packed"):
            result = run_once(strategy)
            runs[strategy].append(result)
            print(
                f"rep {rep} {strategy}: wall={result['wall_sec']:.2f}s "
                f"compiles={result['compiles_actual']} "
                f"(predicted {result['compiles_predicted']}) "
                f"waste={result['padding_waste']:.3f}",
                flush=True,
            )

    summary = {}
    problems = []
    for strategy, results in runs.items():
        hashes = {r["plan_hash"] for r in results}
        if len(hashes) != 1:
            problems.append(f"{strategy}: plan not deterministic ({hashes})")
        predicted = results[0]["compiles_predicted"]
        actuals = {r["compiles_actual"] for r in results}
        if actuals != {predicted}:
            problems.append(
                f"{strategy}: predicted {predicted} compiles, saw {actuals}"
            )
        walls = [r["wall_sec"] for r in results]
        summary[strategy] = {
            "best_wall_sec": round(min(walls), 4),
            "median_wall_sec": round(statistics.median(walls), 4),
            "walls_sec": [round(w, 4) for w in walls],
            "fit_sec": round(min(r["fit_sec"] for r in results), 4),
            "compiles": predicted,
            "buckets": results[0]["buckets"],
            "padding_waste": results[0]["padding_waste"],
            "flops_true": results[0]["flops_true"],
            "flops_padded": results[0]["flops_padded"],
            "plan_hash": results[0]["plan_hash"],
        }

    # member-level numerics: parity-family members share bucket
    # composition AND pad targets across strategies -> identical losses;
    # everything else must at least train to finite losses
    naive_losses = runs["naive"][0]["losses"]
    packed_losses = runs["packed"][0]["losses"]
    parity_delta = max(
        abs(naive_losses[name] - packed_losses[name])
        for name in naive_losses
        if name.startswith("parity-")
    )
    if parity_delta > 1e-9:
        problems.append(
            f"parity members diverged across strategies: {parity_delta}"
        )
    if not all(
        l == l and abs(l) != float("inf")  # NaN/inf guard
        for losses in (naive_losses, packed_losses)
        for l in losses.values()
    ):
        problems.append("non-finite member losses")

    wins = {
        "wall_clock": summary["packed"]["median_wall_sec"]
        < summary["naive"]["median_wall_sec"],
        "compiles": summary["packed"]["compiles"] < summary["naive"]["compiles"],
        "padding_waste": summary["packed"]["padding_waste"]
        < summary["naive"]["padding_waste"],
    }
    doc = {
        "bench": "planner-strategies",
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "reps": REPS,
        "epochs": EPOCHS,
        "members": sum(len(counts) for _, _, _, counts in FLEET),
        "runs": summary,
        "packed_wins": wins,
        "packed_wins_count": sum(wins.values()),
        "parity_member_loss_delta": parity_delta,
        "predicted_matches_actual_compiles": not any(
            "compiles" in p for p in problems
        ),
        "problems": problems,
        "ok": not problems and sum(wins.values()) >= 2,
    }
    out = REPO_ROOT / "BENCH_PLAN.json"
    out.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    print(json.dumps(doc, indent=1, sort_keys=True))
    print(f"wrote {out}")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
