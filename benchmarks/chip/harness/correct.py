"""
The comparison that decides ``correct``.

A run is correct when every operation it attempted produced what the
plain reference produces, within a tolerance written here with its
reason, and when the program contained no fault on the way (it absorbs
device errors and answers from slower paths; only its status document
and its log say so). A build that trains fewer steps or another model is
incorrect, not faster.
"""

import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple

#: largest |program - reference| accepted, as a fraction of
#: max(1, max |reference|), by platform. On a TPU every program of this
#: repo multiplies with bf16-rounded operands (XLA's default matmul
#: precision, and the Pallas kernel alike: ``preferred_element_type``
#: only sets the accumulator); PR 22 measured 0.3%-1.3% of the output
#: scale for the 7-layer hourglass and 0.6% for the 6-layer LSTM against
#: a float32 reference. 5e-2 passes that rounding and fails a wrong
#: program, whose error is of the order of the output. On the CPU every
#: program computes in float32 and 1e-4 holds.
TOLERANCE = {"tpu": 5e-2, "cpu": 1e-4}


class Checks:
    """Collects every failed check of a run, so one run reports all of
    them; ``ok`` is the run's ``correct``."""

    def __init__(self) -> None:
        self.failures: List[str] = []
        self.worst_fraction = 0.0

    @property
    def ok(self) -> bool:
        return not self.failures

    def check(self, ok: Any, what: str) -> bool:
        if not ok:
            self.failures.append(what[:400])
        return bool(ok)

    def compare(self, what: str, got, reference, platform: str) -> None:
        """``got`` against ``reference`` under the platform's tolerance;
        shapes must agree and every value be finite."""
        import numpy as np

        got = np.asarray(got, np.float64)
        reference = np.asarray(reference, np.float64)
        if not self.check(
            got.shape == reference.shape,
            f"{what}: shape {got.shape}, reference {reference.shape}",
        ):
            return
        if not self.check(bool(np.isfinite(got).all()), f"{what}: non-finite values"):
            return
        diff = float(np.max(np.abs(got - reference))) if got.size else 0.0
        scale = max(1.0, float(np.max(np.abs(reference)))) if got.size else 1.0
        self.worst_fraction = max(self.worst_fraction, diff / scale)
        tolerance = TOLERANCE.get(platform, TOLERANCE["tpu"])
        self.check(
            diff <= tolerance * scale,
            f"{what}: max |program - float32 reference| {diff:.3e} exceeds "
            f"{tolerance:.0e} of the output scale {scale:.2f}",
        )


def find_estimator(model: Any) -> Any:
    """The estimator at the end of ``detector.base_estimator`` (a
    Pipeline), which carries ``spec_`` and ``params_``."""
    inner = getattr(model, "base_estimator", model)
    steps = getattr(inner, "steps", None)
    return steps[-1][1] if steps else inner


def host_transform(model: Any, X):
    """The pipeline's transformers ahead of the estimator, on the host."""
    import numpy as np

    inner = getattr(model, "base_estimator", model)
    for _, transformer in (getattr(inner, "steps", None) or [])[:-1]:
        X = transformer.transform(X)
    return np.asarray(X, np.float32)


def load_artifact(output_dir: str, name: str) -> Tuple[Any, Dict[str, Any]]:
    """``(model, build_metadata)`` of one machine of a build."""
    from gordo_tpu import serializer

    directory = os.path.join(output_dir, name)
    metadata = serializer.load_metadata(directory)
    return serializer.load(directory), metadata["metadata"]["build_metadata"]


def check_build_job(
    checks: Checks, job: Dict[str, Any], names: List[str], config: Dict[str, Any]
) -> int:
    """Hold one ``build-fleet`` job to the clean-build rule: exit 0, a
    complete status document with no failed, degraded or fallen-back
    machine and no contained device fault, one artifact a machine, and
    in every artifact finite thresholds and as many training epochs as
    the configuration states. Returns the number of verified artifacts."""
    what = f"job {job.get('index')}"
    output_dir = job["output_dir"]
    checks.check(job["exit_code"] == 0, f"{what}: build-fleet exited {job['exit_code']}")
    try:
        with open(os.path.join(output_dir, "build_status.json")) as f:
            status = json.load(f)
    except (OSError, ValueError) as exc:
        checks.check(False, f"{what}: no build_status.json ({exc})")
        return 0
    checks.check(status.get("state") == "complete", f"{what}: state {status.get('state')}")
    for key in ("failed", "degraded", "fallbacks"):
        checks.check(
            status["machines"].get(key, 0) == 0,
            f"{what}: machines.{key}={status['machines'].get(key)}",
        )
    for key, value in (status.get("robustness") or {}).items():
        checks.check(value == 0, f"{what}: robustness.{key}={value}")
    verified = 0
    for name in names:
        path = os.path.join(output_dir, name, "metadata.json")
        if not checks.check(
            os.path.isfile(path)
            and os.path.isfile(os.path.join(output_dir, name, "model.pkl")),
            f"{what}: {name} has no artifact",
        ):
            continue
        with open(path) as f:
            meta = json.load(f)["metadata"]["build_metadata"]["model"]["model_meta"]
        thresholds = meta.get("feature-thresholds") or []
        losses = (meta.get("history") or {}).get("loss") or []
        ok = checks.check(
            thresholds
            and all(math.isfinite(t) for t in thresholds)
            and math.isfinite(meta.get("aggregate-threshold") or math.nan),
            f"{what}: {name} thresholds missing or non-finite",
        )
        ok &= checks.check(
            len(losses) == config["epochs"] and all(math.isfinite(x) for x in losses),
            f"{what}: {name} trained {len(losses)} epochs, the configuration "
            f"states {config['epochs']} (losses {losses})",
        )
        verified += bool(ok)
    return verified


def check_programs(
    checks: Checks, job: Dict[str, Any], config: Dict[str, Any], samples: int
) -> None:
    """Every fit program of the job scanned at least the samples of the
    history, for the configuration's epochs and batch size: a fit over
    fewer rows or epochs is another build."""
    fits = [p for p in job.get("programs", []) if "fit" in p.get("program", "")]
    checks.check(fits, f"job {job.get('index')}: no fit program span")
    for program in fits:
        checks.check(
            program.get("epochs") == config["epochs"]
            and int(program.get("stacked_samples", 0)) >= samples,
            f"job {job.get('index')}: {program.get('program')} ran "
            f"{program.get('epochs')} epochs over {program.get('stacked_samples')} "
            f"samples; the cell states {config['epochs']} over {samples}",
        )


def check_artifact_forward(
    checks: Checks,
    reference: Any,
    output_dir: str,
    name: str,
    rows: int,
    seed: int,
    platform: str,
) -> None:
    """The artifact's own prediction of ``rows`` seeded rows (on the
    device the process holds) against the reference forward of the
    artifact's own weights."""
    import numpy as np

    from .data import request_rows

    model, metadata = load_artifact(output_dir, name)
    estimator = find_estimator(model)
    hist = metadata["dataset"]["dataset_meta"]["x_hist"]
    X = request_rows(hist, rows, np.random.RandomState(seed))
    got = np.asarray(model.predict(X))
    expected = reference.forward(
        reference.layers_of(estimator),
        reference.model_input(estimator, host_transform(model, X)),
    )
    checks.compare(f"{name} predict", got, expected, platform)


def check_loss_band(
    checks: Checks,
    reference: Any,
    config: Dict[str, Any],
    document: Dict[str, Any],
    output_dir: str,
    name: str,
) -> Optional[Tuple[float, float, float]]:
    """The artifact's final training loss against the band of plain
    reference fits on the same data (``reference.loss_band``; a
    reference without one checks nothing here)."""
    if not hasattr(reference, "loss_band"):
        return None
    import numpy as np

    from gordo_tpu.dataset import GordoBaseDataset  # the data, not the model

    machine = next(m for m in document["machines"] if m["name"] == name)
    X, y = GordoBaseDataset.from_dict(dict(machine["dataset"])).get_data()
    model, metadata = load_artifact(output_dir, name)
    low, high = reference.loss_band(
        host_transform(model, X), np.asarray(y, np.float32), config
    )
    loss = float(metadata["model"]["model_meta"]["history"]["loss"][-1])
    checks.check(
        low <= loss <= high,
        f"{name}: final training loss {loss:.4g} outside the reference band "
        f"[{low:.4g}, {high:.4g}]",
    )
    return loss, low, high
