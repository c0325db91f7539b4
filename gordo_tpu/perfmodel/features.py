"""
Training-row extraction: telemetry spans → (features, target) pairs.

The corpus is what the system already records — nothing new is traced
for the model's benefit:

- ``device_program`` spans (``build_trace.jsonl``, recorded by the
  fleet trainer since PR 3) carry the planner's static features
  (``flops_per_sample``/``stacked_members``/``stacked_samples``/
  ``epochs``) plus the compile-vs-run split; run spans train the
  ``device_ms`` target, compile spans the ``compile_ms`` target: the
  regressor trains on whatever the device actually ran.
- ``serve_batch`` spans (``serve_trace*.jsonl``) carry the fused batch
  shape (``padded_members``/``padded_rows``/``precision``) and, since
  PR 20, ``flops_per_sample`` — each with the measured ``device_ms``
  next to the prediction it will be judged against.
- spans of either kind carrying an ``hbm_bytes`` attribute train the
  peak-HBM target (device-memory sampling is backend-dependent; an
  empty population simply leaves that target analytic).

Discovery reuses the telemetry plane's own machinery
(:func:`~gordo_tpu.telemetry.trace_analysis.trace_bases` +
:func:`~gordo_tpu.telemetry.trace_analysis.read_traces`), so rotated
generations and per-worker sink variants merge exactly the way
``gordo-tpu trace`` reads them. The dependency arrow points
perfmodel→telemetry; telemetry stays stdlib-only.
"""

import hashlib
import logging
import os
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

from ..planner.costmodel import learned_feature_vector
from ..telemetry import SERVE_TRACE_FILE
from ..telemetry.progress import BUILD_TRACE_FILE
from ..telemetry.trace_analysis import read_trace, read_traces, trace_bases

logger = logging.getLogger(__name__)


class TrainingRow(NamedTuple):
    """One harvested sample: a feature vector and its measured target."""

    target: str  # device_ms | compile_ms | hbm_bytes
    program: str  # fleet_fit / fleet_windowed_fit / fleet_forward / ...
    features: Tuple[float, ...]  # the LEARNED_FEATURES vector
    y: float  # measured value in the target's unit (ms or bytes)


def _float(value: Any) -> Optional[float]:
    try:
        out = float(value)
    except (TypeError, ValueError):
        return None
    return out


def _shape_of(attrs: Dict[str, Any]) -> Optional[Tuple[float, int, int, int]]:
    """(flops_per_sample, members, rows, epochs) from span attributes,
    or None when the static features are missing (older traces)."""
    flops = _float(attrs.get("flops_per_sample"))
    if flops is None or flops < 0.0:
        return None
    try:
        members = int(
            attrs.get("stacked_members")
            or attrs.get("padded_members")
            or attrs.get("members")
            or 0
        )
        rows = int(
            attrs.get("stacked_samples") or attrs.get("padded_rows") or 0
        )
        epochs = int(attrs.get("epochs") or 1)
    except (TypeError, ValueError):
        return None
    if members <= 0 or rows <= 0:
        return None
    return flops, members, rows, epochs


def rows_from_spans(spans: Iterable[dict]) -> List[TrainingRow]:
    """Every usable training row in ``spans``; rows with missing static
    features or missing/zero targets are skipped, never guessed."""
    out: List[TrainingRow] = []
    for span in spans:
        if not isinstance(span, dict):
            continue
        name = span.get("name")
        attrs = span.get("attributes") or {}
        if name == "device_program":
            program = str(attrs.get("program") or "")
            shape = _shape_of(attrs)
            if not program or shape is None:
                continue
            flops, members, rows, epochs = shape
            precision = attrs.get("precision")
            device_ms = _float(attrs.get("device_ms"))
            if device_ms is None:
                device_ms = _float(span.get("duration_ms"))
            if attrs.get("compile"):
                # compile cost tracks program complexity, not data
                # volume: shape axes pin to 1, mirroring
                # CostModel.predict_compile_s's evaluation
                if device_ms is not None and device_ms > 0.0:
                    out.append(
                        TrainingRow(
                            "compile_ms",
                            program,
                            tuple(
                                learned_feature_vector(
                                    flops, 1, 1, 1, precision
                                )
                            ),
                            device_ms,
                        )
                    )
            elif device_ms is not None and device_ms > 0.0:
                out.append(
                    TrainingRow(
                        "device_ms",
                        program,
                        tuple(
                            learned_feature_vector(
                                flops, members, rows, epochs, precision
                            )
                        ),
                        device_ms,
                    )
                )
        elif name == "serve_batch":
            shape = _shape_of(attrs)
            if shape is None:
                continue
            flops, members, rows, _ = shape
            precision = attrs.get("precision")
            device_ms = _float(attrs.get("device_ms"))
            if device_ms is None or device_ms <= 0.0:
                continue
            out.append(
                TrainingRow(
                    "device_ms",
                    "fleet_forward",
                    tuple(
                        learned_feature_vector(
                            flops, members, rows, 1, precision
                        )
                    ),
                    device_ms,
                )
            )
        else:
            continue
        # either span kind may additionally carry a measured HBM peak
        hbm = _float(attrs.get("hbm_bytes"))
        if hbm is not None and hbm > 0.0:
            shape = _shape_of(attrs)
            if shape is None:
                continue
            flops, members, rows, _ = shape
            program = (
                "fleet_forward"
                if name == "serve_batch"
                else str(attrs.get("program") or "")
            )
            if program:
                out.append(
                    TrainingRow(
                        "hbm_bytes",
                        program,
                        tuple(
                            learned_feature_vector(
                                flops,
                                members,
                                rows,
                                1,
                                attrs.get("precision"),
                            )
                        ),
                        hbm,
                    )
                )
    return out


def harvest_trace(path: str) -> List[TrainingRow]:
    """Training rows from ONE trace file (rotated generations of the
    base are read automatically by the caller passing each)."""
    return rows_from_spans(read_trace(path))


def harvest_corpus(directory: str) -> Tuple[List[TrainingRow], Dict[str, Any]]:
    """Training rows from every trace in ``directory`` (a build output
    dir or serving telemetry dir): the build trace and the serve trace,
    each with its rotated generations and per-worker sink variants
    merged the same way ``gordo-tpu trace`` merges them. Returns
    ``(rows, stats)``; an empty/absent corpus is ``([], stats)``, never
    an error — cold start falls back analytic."""
    stats: Dict[str, Any] = {"directory": directory, "traces": [], "spans": 0}
    rows: List[TrainingRow] = []
    if not os.path.isdir(directory):
        return rows, stats
    for base_name in (BUILD_TRACE_FILE, SERVE_TRACE_FILE):
        bases = trace_bases(directory, base_name)
        if not bases:
            continue
        spans = list(read_traces(bases))
        stats["traces"].append({"base": base_name, "sinks": len(bases)})
        stats["spans"] += len(spans)
        rows.extend(rows_from_spans(spans))
    stats["rows"] = len(rows)
    by_key: Dict[str, int] = {}
    for row in rows:
        key = f"{row.target}/{row.program}"
        by_key[key] = by_key.get(key, 0) + 1
    stats["rows_by_model"] = dict(sorted(by_key.items()))
    return rows, stats


def corpus_fingerprint(rows: Iterable[TrainingRow]) -> str:
    """A stable identity for a training corpus — recalibration skips
    refitting when the corpus has not changed since the incumbent fit.
    Order-independent (worker sink merge order is not deterministic)."""
    digest = hashlib.sha256()
    for line in sorted(
        f"{r.target}|{r.program}|{','.join(f'{x:.6f}' for x in r.features)}"
        f"|{r.y:.6f}"
        for r in rows
    ):
        digest.update(line.encode())
        digest.update(b"\0")
    return digest.hexdigest()[:16]
