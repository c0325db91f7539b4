"""What a finished ``build-fleet`` job left behind, as plain records."""

import datetime
import json
import os
from typing import Any, Dict, List


def _epoch(stamp: str) -> float:
    return datetime.datetime.fromisoformat(stamp).timestamp()


def read_spans(output_dir: str) -> Dict[str, List[Dict[str, Any]]]:
    """``build_trace.jsonl`` of a job: its ``device_program`` spans
    (with the static features the trainer stamps on them) and its
    ``build_phase`` spans, each with wall-clock start and end."""
    programs: List[Dict[str, Any]] = []
    phases: List[Dict[str, Any]] = []
    path = os.path.join(output_dir, "build_trace.jsonl")
    if not os.path.isfile(path):
        return {"programs": programs, "phases": phases}
    with open(path) as f:
        for line in f:
            try:
                span = json.loads(line)
            except ValueError:
                continue
            record = {
                "start": _epoch(span["start_time"]),
                "end": _epoch(span["end_time"]),
                "ms": span.get("duration_ms"),
                **(span.get("attributes") or {}),
            }
            if span.get("name") == "device_program":
                programs.append(record)
            elif span.get("name") == "build_phase":
                phases.append(record)
    return {"programs": programs, "phases": phases}


def read_status(output_dir: str) -> Dict[str, Any]:
    try:
        with open(os.path.join(output_dir, "build_status.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}
