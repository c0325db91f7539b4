#!/usr/bin/env python3
"""
One ``build-fleet`` job of a chip-benchmark cell with its fold models
scored both ways from the same predictions, on the device JAX finds: by
the group's predict program (``parallel/fleet.fold_scores``, what the
build keeps) and by the host's per-machine-fold sklearn and numpy code
on the predictions fetched from that program.

    python3 scripts/fold_score_parity.py [--cell hourglass_build] [--cell lstm_build] [--seed N]

Prints one JSON object a cell (and writes them to
``chiprun_out/fold_score_parity.json``): the machine-folds compared, the
feature thresholds that differ at all (0 is the claim), and the largest
deviation of the aggregate thresholds, the squared and absolute errors
(relative to the value) and of R2 and explained variance (relative to the
value, or to 1 where the value is smaller: they are shares of 1). A
builder's tool, not a test: the tier-1 tests hold the
same comparison at toy sizes on the CPU (tests/parallel/test_fold_scores.py).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

#: metrics that are shares of 1: compared on a scale of at least 1
SHARES = ("explained-variance-score", "r2-score")


def compare_cell(cell: str, seed: int) -> dict:
    import numpy as np

    from harness.data import machines_document
    from harness.manifest import Cell, load_manifest

    from gordo_tpu.machine import Machine
    from gordo_tpu.parallel import FleetBuilder
    from gordo_tpu.parallel.fleet import fetch_to_host

    resolved = Cell(load_manifest(ROOT), cell, ROOT)
    config, traffic = resolved.config, resolved.traffic
    document = machines_document(
        config, seed, 0, traffic["machines_per_job"], traffic["history_days"]
    )
    machines = [
        Machine.from_dict({**m, "project_name": document["project_name"]})
        for m in document["machines"]
    ]

    class BothWays(FleetBuilder):
        """Adopts the program's scores, then scores the same predictions
        with the host's code into shadows of the plans' books."""

        shadow_scores: dict = {}
        shadow_state: dict = {}

        def _adopt_fold_scores(self, group, fold_rows, scoring, predicted, fold_state):
            super()._adopt_fold_scores(group, fold_rows, scoring, predicted, fold_state)
            assert scoring is not None, "the cell's evaluation fell back to the host"
            predictions = np.asarray(fetch_to_host(predicted[0]))[: len(group)]
            kept = [plan.cv_scores for plan, _ in group]
            for plan, _ in group:
                plan.cv_scores = self.shadow_scores.setdefault(plan.machine.name, {})
                self.shadow_state.setdefault(plan.machine.name, {})
            try:
                super()._adopt_fold_scores(
                    group, fold_rows, None, predictions, self.shadow_state
                )
            finally:
                for (plan, _), scores in zip(group, kept):
                    plan.cv_scores = scores

    builder = BothWays(machines)
    finalize = builder._finalize_cv
    began = time.time()
    plans, fallbacks = builder._plan_all()
    assert not fallbacks
    plans = builder._load_all_data(plans)
    compared = {}

    def compare(plan, state):
        # before _finalize_cv adds the fold summaries to the scores
        scores, shadow = plan.cv_scores, BothWays.shadow_scores[plan.machine.name]
        assert list(scores) == list(shadow)
        for key, folds in scores.items():
            assert list(folds) == list(shadow[key])
            share = key.startswith(SHARES)
            for fold, value in folds.items():
                other = shadow[key][fold]
                floor = 1.0 if share else 1e-30
                deviation = abs(value - other) / max(abs(value), abs(other), floor)
                name = "shares_of_scale" if share else "errors_rel"
                compared[name] = max(compared.get(name, 0.0), deviation)
        host = BothWays.shadow_state[plan.machine.name]
        assert list(state) == list(host)
        for fold, series in state["feature_folds"].items():
            differ = int((series.to_numpy() != host["feature_folds"][fold].to_numpy()).sum())
            compared["feature_thresholds_differing"] = (
                compared.get("feature_thresholds_differing", 0) + differ
            )
            compared["feature_thresholds"] = compared.get("feature_thresholds", 0) + len(series)
            value, other = state["agg_folds"][fold], host["agg_folds"][fold]
            compared["aggregate_threshold_rel"] = max(
                compared.get("aggregate_threshold_rel", 0.0),
                abs(value - other) / max(abs(value), abs(other), 1e-30),
            )
            compared["machine_folds"] = compared.get("machine_folds", 0) + 1
        finalize(plan, state)

    builder._finalize_cv = compare
    builder._run_cross_validation(plans)
    assert not builder.build_errors, builder.build_errors
    import jax

    device = jax.devices()[0]
    return {
        "cell": cell, "seed": seed, "machines": len(machines),
        "device": f"{device.platform} {device.device_kind}",
        "seconds": round(time.time() - began, 2), **compared,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", action="append")
    parser.add_argument("--seed", type=int, default=2147483659)
    args = parser.parse_args(argv)
    lines = [
        compare_cell(cell, args.seed)
        for cell in args.cell or ["hourglass_build", "lstm_build"]
    ]
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "fold_score_parity.json"), "w") as f:
        json.dump(lines, f, indent=1)
    for line in lines:
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
