"""
Offline analysis of the JSONL span traces (``serve_trace.jsonl`` /
``build_trace.jsonl``): the library behind ``gordo-tpu trace``.

The traces answer "where does the time go" only if something aggregates
them; this module turns a span stream into:

- **per-span-name latency distributions** (count, p50/p95/p99, total) —
  the serve trace's ``request``/``serve_batch``/stage spans, the build
  trace's ``build_phase``/``device_program`` spans;
- **the request breakdown**: for every ``request`` span, its child
  stage spans are joined back by ``(trace_id, parent_id)`` and the
  aggregate reports per-stage percentiles, each stage's share of median
  request walltime, and the **attribution coverage** — the fraction of
  request walltime the instrumented stages explain (the serving
  observability acceptance bar is ≥0.9; anything below means the
  pipeline has un-instrumented host work);
- **the critical path** of the median-ish request: its own stages,
  longest first;
- **top self-time frames** aggregated across ``profile`` spans (the
  sampling profiler's output), by (stage, function).

Everything is computed from span dicts alone — the analyses run on any
trace the :class:`~gordo_tpu.telemetry.SpanRecorder` wrote, rotated
generations included. Stdlib-only, like the whole telemetry package.
"""

import json
import os
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

#: serving stage spans whose parent is the request span; anything else
#: under a request (events, nested helper spans) is excluded from the
#: stage breakdown so shares stay a partition of walltime. The
#: streaming-plane spans are root spans with their own breakdown
#: (:func:`stream_breakdown`), never request stages.
_NON_STAGE_NAMES = (
    "request",
    "profile",
    "stream_ingest",
    "stream_score",
    "stream_emit",
)


def trace_bases(directory: str, base_name: str) -> List[str]:
    """Every base sink path for one logical trace in ``directory``: the
    shared spelling plus the per-worker ``<stem>-<pid>`` variants the
    worker-sink split writes (rotated generations ride each base)."""
    from .aggregate import sink_bases

    return sink_bases(directory, base_name)


def iter_trace_files(
    path: str,
    include_rotated: bool = True,
    since_ts: Optional[float] = None,
    window_index: Optional[Dict[str, Dict[str, Any]]] = None,
) -> List[str]:
    """The physical files of one trace sink, oldest first — the rollup
    reader's generation discovery (``aggregate.generation_files``, a
    directory listing rather than a ``.1``-exists probe walk: mid-
    rotation the ``.1`` slot is briefly empty while higher generations
    still hold bytes, and a probe walk goes blind to the whole chain).
    With ``since_ts``, rotated generations are skipped wholesale when
    provably pre-cutoff: by the rollup manifest's span-time window
    (``window_index``, keyed by basename — ``aggregate.sink_window_
    index``; authoritative when the generation was read ``complete``),
    else by mtime — a generation's mtime is its LAST write, so every
    span in it is older than the cutoff. This is what keeps ``gordo-tpu
    trace --since`` from re-parsing a week-old 256MiB corpus."""
    from .aggregate import generation_files

    if include_rotated:
        paths = generation_files(path)
    else:
        paths = [path] if os.path.exists(path) else []
    if since_ts is None:
        return paths
    kept = []
    for trace_path in paths:
        if trace_path != path:  # the live file always stays
            entry = (window_index or {}).get(os.path.basename(trace_path))
            if entry and entry.get("complete"):
                max_ts = entry.get("max_ts")
                if max_ts is not None and float(max_ts) < since_ts:
                    continue
                kept.append(trace_path)
                continue
            try:
                if os.path.getmtime(trace_path) < since_ts:
                    continue
            except OSError:
                continue
        kept.append(trace_path)
    return kept


def _span_end_ts(span: dict) -> Optional[float]:
    from .aggregate import parse_span_time

    return parse_span_time(span.get("end_time"))


def read_trace(
    path: str,
    include_rotated: bool = True,
    since_ts: Optional[float] = None,
    until_ts: Optional[float] = None,
    window_index: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Iterator[dict]:
    """Yield span dicts from a JSONL trace file, oldest first across
    rotated generations (``p.N`` ... ``p.1``, then ``p``). Unparseable
    lines (a crash mid-write leaves at most one) are skipped. With a
    time window, spans ending outside [since_ts, until_ts] are dropped
    and pre-cutoff generations are never opened at all."""
    for trace_path in iter_trace_files(
        path, include_rotated, since_ts, window_index=window_index
    ):
        try:
            handle = open(trace_path)
        except OSError:
            continue  # rotated away between discovery and open
        with handle:
            for line in handle:
                line = line.strip()
                if not line:
                    continue
                try:
                    span = json.loads(line)
                except ValueError:
                    continue
                if not (isinstance(span, dict) and "name" in span):
                    continue
                if since_ts is not None or until_ts is not None:
                    end_ts = _span_end_ts(span)
                    if end_ts is None:
                        continue
                    if since_ts is not None and end_ts < since_ts:
                        continue
                    if until_ts is not None and end_ts > until_ts:
                        continue
                yield span


def read_traces(
    paths: List[str],
    since_ts: Optional[float] = None,
    until_ts: Optional[float] = None,
    window_index: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Iterator[dict]:
    """Spans from several sink bases (N workers' traces), deduplicated
    by ``(trace_id, span_id)`` — the merge contract shared with the
    rollup reducer."""
    seen: set = set()
    for path in paths:
        for span in read_trace(
            path,
            since_ts=since_ts,
            until_ts=until_ts,
            window_index=window_index,
        ):
            context = span.get("context") or {}
            key = (context.get("trace_id", ""), context.get("span_id", ""))
            if key != ("", ""):
                if key in seen:
                    continue
                seen.add(key)
            yield span


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of ``values`` (must be sorted)."""
    if not values:
        return 0.0
    rank = max(0, min(len(values) - 1, int(round(q * (len(values) - 1)))))
    return values[rank]


def _distribution(durations: List[float]) -> Dict[str, float]:
    durations = sorted(durations)
    return {
        "count": len(durations),
        "p50_ms": round(percentile(durations, 0.50), 3),
        "p95_ms": round(percentile(durations, 0.95), 3),
        "p99_ms": round(percentile(durations, 0.99), 3),
        "total_ms": round(sum(durations), 3),
    }


def summarize_spans(spans: Iterable[dict]) -> Dict[str, Dict[str, float]]:
    """Per-span-name duration distributions, skipping point events."""
    by_name: Dict[str, List[float]] = {}
    for span in spans:
        if span.get("kind") == "event":
            continue
        by_name.setdefault(span["name"], []).append(
            float(span.get("duration_ms", 0.0))
        )
    return {
        name: _distribution(durations)
        for name, durations in sorted(by_name.items())
    }


def request_breakdown(spans: Iterable[dict]) -> Optional[Dict[str, Any]]:
    """
    The per-stage attribution of the trace's ``request`` spans:

    ``stages`` maps stage name → distribution + ``share_of_p50`` (the
    stage's median as a fraction of the median request walltime);
    ``attribution_coverage`` is the summed share — how much of a median
    request the instrumented stages explain; ``critical_path`` lists the
    median request's own stages, longest first. None when the trace
    holds no request spans.
    """
    requests: List[dict] = []
    children: Dict[Tuple[str, str], List[dict]] = {}
    for span in spans:
        if span.get("kind") == "event":
            continue
        context = span.get("context") or {}
        if span["name"] == "request":
            requests.append(span)
        elif (
            span["name"] not in _NON_STAGE_NAMES
            and span.get("parent_id")
        ):
            children.setdefault(
                (context.get("trace_id", ""), span["parent_id"]), []
            ).append(span)
    if not requests:
        return None

    walltimes = sorted(float(r.get("duration_ms", 0.0)) for r in requests)
    p50_wall = percentile(walltimes, 0.50)
    stage_durations: Dict[str, List[float]] = {}
    # attribution coverage is computed PER REQUEST (own stages summed
    # over own walltime, then the median ratio) — aggregating means
    # against a median walltime overstates coverage whenever the
    # latency distribution is skewed, which under concurrency it
    # always is
    coverage_ratios: List[float] = []
    for request in requests:
        context = request.get("context") or {}
        trace_id = context.get("trace_id", "")
        own = children.get((trace_id, context.get("span_id", "")), [])
        for stage in own:
            stage_durations.setdefault(stage["name"], []).append(
                float(stage.get("duration_ms", 0.0))
            )
            # one level of nesting: spans recorded while a stage was
            # open (the micro-batcher's queue_wait / batch_* intervals
            # land inside `inference`) surface as stages of their own —
            # informational sub-segments, excluded from coverage below
            # (their time is already inside their parent stage's)
            stage_context = stage.get("context") or {}
            for nested in children.get(
                (trace_id, stage_context.get("span_id", "")), []
            ):
                stage_durations.setdefault(nested["name"], []).append(
                    float(nested.get("duration_ms", 0.0))
                )
        wall = float(request.get("duration_ms", 0.0))
        if wall > 0:
            explained = sum(
                float(stage.get("duration_ms", 0.0)) for stage in own
            )
            coverage_ratios.append(min(1.0, explained / wall))
    coverage = percentile(sorted(coverage_ratios), 0.50)

    stages: Dict[str, Dict[str, float]] = {}
    for name, durations in sorted(stage_durations.items()):
        dist = _distribution(durations)
        # the stage's conditional median over the median request
        # walltime — how much of a typical request this stage explains
        # when it occurs (queue_wait occurs only for batched requests)
        dist["share_of_p50"] = round(
            dist["p50_ms"] / p50_wall if p50_wall > 0 else 0.0, 4
        )
        stages[name] = dist

    # the critical path of the median request: the request whose
    # walltime sits at p50, its own stages longest-first
    median_request = min(
        requests,
        key=lambda r: abs(float(r.get("duration_ms", 0.0)) - p50_wall),
    )
    context = median_request.get("context") or {}
    own = children.get(
        (context.get("trace_id", ""), context.get("span_id", "")), []
    )
    critical_path = [
        {
            "stage": stage["name"],
            "duration_ms": round(float(stage.get("duration_ms", 0.0)), 3),
        }
        for stage in sorted(
            own, key=lambda s: float(s.get("duration_ms", 0.0)), reverse=True
        )
    ]

    return {
        "requests": len(requests),
        "walltime_p50_ms": round(p50_wall, 3),
        "walltime_p95_ms": round(percentile(walltimes, 0.95), 3),
        "walltime_p99_ms": round(percentile(walltimes, 0.99), 3),
        "stages": stages,
        "attribution_coverage": round(coverage, 4),
        "critical_path": critical_path,
    }


def stream_breakdown(spans: Iterable[dict]) -> Optional[Dict[str, Any]]:
    """
    The streaming plane's per-session critical path: for every stream id
    seen in the trace, the ``stream_ingest`` → ``stream_score`` →
    ``stream_emit`` stage distributions, the freshness numbers the score
    spans carry (ingest→scored lag p50/max), device time vs the cost
    model's prediction, and the row/shed accounting summed from span
    attributes. ``linked_ingests`` counts the OTel links score spans
    carry back to the ingests they drained — the fraction of flushes a
    trace reader can walk end-to-end. None when the trace holds no
    streaming spans.
    """
    stage_names = ("stream_ingest", "stream_score", "stream_emit")
    by_stream: Dict[str, Dict[str, Any]] = {}
    for span in spans:
        name = span.get("name")
        if name not in stage_names:
            continue
        attributes = span.get("attributes") or {}
        stream_id = str(attributes.get("stream") or "-")
        entry = by_stream.setdefault(
            stream_id,
            {
                "durations": {stage: [] for stage in stage_names},
                "device_ms": [],
                "predicted_device_ms": [],
                "lag_p50_ms": [],
                "lag_max_ms": 0.0,
                "rows_in": 0,
                "rows_scored": 0,
                "rows_failed": 0,
                "rows_shed": 0,
                "windows": 0,
                "events": 0,
                "linked_ingests": 0,
            },
        )
        entry["durations"][name].append(
            float(span.get("duration_ms", 0.0))
        )
        if name == "stream_ingest":
            entry["rows_in"] += int(attributes.get("rows", 0) or 0)
        elif name == "stream_score":
            scored = attributes.get("rows_scored")
            if scored is None:
                scored = attributes.get("rows", 0)
            entry["rows_scored"] += int(scored or 0)
            entry["rows_failed"] += int(
                attributes.get("rows_failed", 0) or 0
            )
            entry["rows_shed"] += int(attributes.get("shed", 0) or 0)
            entry["windows"] += int(attributes.get("windows", 0) or 0)
            entry["linked_ingests"] += len(span.get("links") or [])
            device = attributes.get("device_ms")
            if device is not None:
                entry["device_ms"].append(float(device))
            predicted = attributes.get("predicted_device_ms")
            if predicted is not None and float(predicted) >= 0.0:
                entry["predicted_device_ms"].append(float(predicted))
            lag_p50 = attributes.get("lag_p50_ms")
            if lag_p50 is not None:
                entry["lag_p50_ms"].append(float(lag_p50))
            lag_max = attributes.get("lag_max_ms")
            if lag_max is not None:
                entry["lag_max_ms"] = max(
                    entry["lag_max_ms"], float(lag_max)
                )
        else:
            entry["events"] += int(attributes.get("events", 0) or 0)
    if not by_stream:
        return None

    streams: Dict[str, Dict[str, Any]] = {}
    for stream_id, entry in sorted(by_stream.items()):
        stages = {
            stage: _distribution(durations)
            for stage, durations in entry["durations"].items()
            if durations
        }
        lag_p50s = sorted(entry["lag_p50_ms"])
        device = sorted(entry["device_ms"])
        predicted = sorted(entry["predicted_device_ms"])
        # the session's median critical path, in pipeline order: what
        # one row pays from ingest acceptance to the emitted event
        critical_path = [
            {
                "stage": stage,
                "p50_ms": stages[stage]["p50_ms"],
            }
            for stage in stage_names
            if stage in stages
        ]
        streams[stream_id] = {
            "stages": stages,
            "flushes": stages.get("stream_score", {}).get("count", 0),
            "rows_in": entry["rows_in"],
            "rows_scored": entry["rows_scored"],
            "rows_failed": entry["rows_failed"],
            "rows_shed": entry["rows_shed"],
            "windows": entry["windows"],
            "events": entry["events"],
            "linked_ingests": entry["linked_ingests"],
            "lag_p50_ms": round(percentile(lag_p50s, 0.50), 3),
            "lag_max_ms": round(entry["lag_max_ms"], 3),
            "device_p50_ms": round(percentile(device, 0.50), 3),
            "predicted_device_p50_ms": (
                round(percentile(predicted, 0.50), 3)
                if predicted
                else None
            ),
            "critical_path": critical_path,
        }
    return {
        "streams": streams,
        "totals": {
            "rows_in": sum(s["rows_in"] for s in streams.values()),
            "rows_scored": sum(
                s["rows_scored"] for s in streams.values()
            ),
            "rows_failed": sum(
                s["rows_failed"] for s in streams.values()
            ),
            "rows_shed": sum(s["rows_shed"] for s in streams.values()),
            "flushes": sum(s["flushes"] for s in streams.values()),
        },
    }


def _covered_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def build_breakdown(spans: Iterable[dict]) -> Optional[Dict[str, Any]]:
    """
    Where a fleet build's time went, from its ``build_phase`` and
    ``build_part`` spans: per phase (summed over its re-entries) the wall
    ``seconds``, the ``parts`` recorded inside it (thread-seconds and
    count: a pooled phase's parts can exceed its wall seconds; a device
    program run in the phase is listed as ``program <name>``, a fit
    program with the ``validation_slots`` of its buckets summed and, for
    the dense fit, the widest ``shuffle_columns`` among them; a predict
    program with its ``members`` and, of them, those whose parameters
    it took from the device, ``params_resident_members``; a program of
    ``sparse_attention`` layers with the blocks of queries a window a
    layer whose selection searches, ``selection_blocks_searched``; a fit of experts
    gated by ``relu`` with its ``gate_active`` and ``gate_total`` summed; a
    fit of latent attention with what a row keeps of itself,
    ``kv_lora_rank`` + ``qk_rope_head_dim`` of ``kv_expanded_dim``; a fit
    of state-space layers with what its scans hold and which layers read
    an earlier layer's tensors, ``ssm_inner``, ``ssm_state``,
    ``scan_chunk``, ``memory_reads``, ``kv_reads``) and its
    ``self_seconds``, the wall time no part or program covers. Where its
    spans carry them, a phase also has ``cpu_seconds`` (its own thread's)
    and ``process_cpu_seconds`` (every thread's between its two ends),
    and a part ``cpu_seconds``, ``bytes`` and, a ``collect``, the
    ``d2h_seconds`` of its fetch alone: an attribute of the ``collect``
    entry and no part of its own, so nothing counts it twice (and the
    ``bytes_deferred`` it did not wait for, which ``dump``'s ``write``
    meets again as ``bytes_fetched_beside_write``). A part
    covers its own interval (overlapping parts count once); a part
    recorded as a sum over many pieces (``count`` attribute) covers its
    seconds. Only parts whose parent is the phase cover it: a part
    nested in another (the fetch of predictions inside its program; the
    dataset's parts, which ``machine_fetch`` carries as ``<part>_s``
    attributes) is in the table and already inside its parent's
    interval. ``compile`` is the compile path's seconds from the
    ``fleet_build`` root span. None when the trace holds no build phases.
    """
    from .aggregate import parse_span_time
    from .device import COMPILE_PATH_KEYS
    from .recorder import (
        PART_SUMS,
        PHASE_SUMS,
        add_sums,
        nested_part_cpu_seconds,
        nested_part_seconds,
        part_sums,
    )

    phases: Dict[str, Dict[str, Any]] = {}
    phase_spans: Dict[str, Tuple[str, float, float]] = {}
    parts: List[dict] = []
    compile_path = None
    for span in spans:
        name = span.get("name")
        attributes = span.get("attributes") or {}
        seconds = float(span.get("duration_ms", 0.0)) / 1000.0
        if name == "build_phase":
            phase = str(attributes.get("phase", ""))
            entry = phases.setdefault(
                phase, {"entries": 0, "seconds": 0.0, "self_seconds": 0.0, "parts": {}}
            )
            entry["entries"] += 1
            entry["seconds"] += seconds
            add_sums(entry, PHASE_SUMS, attributes)
            start = parse_span_time(span.get("start_time")) or 0.0
            span_id = (span.get("context") or {}).get("span_id", "")
            phase_spans[span_id] = (phase, start, start + seconds)
        elif name in ("build_part", "device_program"):
            parts.append(span)
        elif name == "fleet_build" and "trace_s" in attributes:
            compile_path = {
                key: attributes[key] for key in COMPILE_PATH_KEYS if key in attributes
            }
    if not phases:
        return None
    covering: Dict[str, List[Tuple[float, float]]] = {}
    summed: Dict[str, float] = {}

    def add(phase: str, label: str, seconds: float, count: int, sums) -> None:
        part = phases[phase]["parts"].setdefault(label, {"seconds": 0.0, "count": 0})
        part["seconds"] += seconds
        part["count"] += count
        add_sums(part, PART_SUMS, sums)

    for span in parts:
        attributes = span.get("attributes") or {}
        seconds = float(span.get("duration_ms", 0.0)) / 1000.0
        parent = span.get("parent_id") or ""
        if span["name"] == "device_program":
            # a program covers its phase like a part; it names no phase
            phase = phase_spans[parent][0] if parent in phase_spans else ""
            label = f"program {attributes.get('program', '')}"
        else:
            phase = str(attributes.get("phase", ""))
            label = str(attributes.get("part", ""))
        if phase in phases:
            sums = (
                part_sums(attributes)
                if span["name"] == "build_part"
                # a program's ``bytes`` are its bucket's staged size, not
                # what the span moved
                else {"cpu_seconds": attributes.get("cpu_seconds")}
            )
            add(phase, label, seconds, int(attributes.get("count", 1)), sums)
            part = phases[phase]["parts"][label]
            if "validation_slots" in attributes:  # a fit program's span
                part["validation_slots"] = part.get("validation_slots", 0) + int(
                    attributes["validation_slots"]
                )
            if "shuffle_columns" in attributes:  # a dense fit program's span
                part["shuffle_columns"] = max(
                    part.get("shuffle_columns", 0), int(attributes["shuffle_columns"])
                )
            if "params_resident_members" in attributes:  # a predict program's span
                for key in ("members", "params_resident_members"):
                    part[key] = part.get(key, 0) + int(attributes.get(key) or 0)
            if "selection_blocks_searched" in attributes:  # a program of sparse attention
                part["selection_blocks_searched"] = int(attributes["selection_blocks_searched"])
            if "gate_total" in attributes:  # a fit of experts gated by relu: a list a layer
                for key in ("gate_active", "gate_total"):
                    part[key] = part.get(key, 0.0) + float(sum(attributes.get(key) or ()))
            if attributes.get("kv_lora_rank"):  # a fit of latent attention: what a row keeps
                for key in ("kv_lora_rank", "qk_rope_head_dim", "kv_expanded_dim"):
                    part[key] = int(attributes[key])
            if attributes.get("ssm_inner"):  # a fit of state-space layers: what its scans hold
                for key in ("ssm_inner", "ssm_state", "scan_chunk", "memory_reads", "kv_reads"):
                    part[key] = attributes[key]
            if span["name"] == "build_part":
                nested_cpu = nested_part_cpu_seconds(attributes)
                for nested, nested_seconds in nested_part_seconds(attributes).items():
                    add(
                        phase, nested, nested_seconds, 1,
                        {"cpu_seconds": nested_cpu.get(nested)},
                    )
        if parent not in phase_spans:
            continue
        if "count" in attributes:
            summed[parent] = summed.get(parent, 0.0) + seconds
            continue
        _, phase_start, phase_end = phase_spans[parent]
        start = parse_span_time(span.get("start_time")) or 0.0
        start, end = max(start, phase_start), min(start + seconds, phase_end)
        if end > start:
            covering.setdefault(parent, []).append((start, end))
    for span_id, (phase, start, end) in phase_spans.items():
        covered = _covered_seconds(covering.get(span_id, [])) + summed.get(span_id, 0.0)
        phases[phase]["self_seconds"] += max(0.0, (end - start) - covered)
    for entry in phases.values():
        for key in ("seconds", "self_seconds") + PHASE_SUMS:
            if key in entry:
                entry[key] = round(entry[key], 6)
        for part in entry["parts"].values():
            for key in ("seconds", "cpu_seconds", "d2h_seconds", "fetch_wait_seconds"):
                if key in part:
                    part[key] = round(part[key], 6)
    return {"phases": phases, "compile": compile_path}


def prediction_accuracy(
    spans: Iterable[dict],
) -> Optional[Dict[str, Dict[str, Any]]]:
    """Predicted-vs-actual device time per program population: every
    span carrying both a measured ``device_ms`` and the cost model's
    ``predicted_device_ms`` stamp (serve batches, stream flushes) is one
    scored pair. ``error_p50``/``error_p95`` are relative-error
    percentiles (|predicted − actual| / actual); ``bias`` is the median
    predicted/actual ratio — above 1.0 the model over-predicts, below it
    under-predicts. The ``-1.0`` predicted sentinel (estimator
    unavailable) is excluded, so accuracy never averages in the spans
    that had no prediction at all."""
    by_key: Dict[str, Dict[str, list]] = {}
    for span in spans:
        attributes = span.get("attributes") or {}
        try:
            device = float(attributes.get("device_ms"))
            predicted = float(attributes.get("predicted_device_ms"))
        except (TypeError, ValueError):
            continue
        if device <= 0.0 or predicted < 0.0:
            continue
        key = str(attributes.get("program") or span["name"])
        entry = by_key.setdefault(key, {"ratios": [], "errors": []})
        entry["ratios"].append(predicted / device)
        entry["errors"].append(abs(predicted - device) / device)
    if not by_key:
        return None
    out: Dict[str, Dict[str, Any]] = {}
    for key, entry in sorted(by_key.items()):
        errors = sorted(entry["errors"])
        ratios = sorted(entry["ratios"])
        out[key] = {
            "count": len(errors),
            "error_p50": round(percentile(errors, 0.50), 4),
            "error_p95": round(percentile(errors, 0.95), 4),
            "bias": round(percentile(ratios, 0.50), 4),
        }
    return out


def top_profile_frames(
    spans: Iterable[dict], max_frames: int = 25
) -> List[Dict[str, Any]]:
    """Self-time frames aggregated across every ``profile`` span in the
    trace, by (stage, function), heaviest first."""
    totals: Dict[Tuple[str, str], Dict[str, float]] = {}
    for span in spans:
        if span["name"] != "profile":
            continue
        for frame in (span.get("attributes") or {}).get("frames", []):
            key = (frame.get("stage", "-"), frame.get("function", "?"))
            entry = totals.setdefault(key, {"self_ms": 0.0, "samples": 0})
            entry["self_ms"] += float(frame.get("self_ms", 0.0))
            entry["samples"] += int(frame.get("samples", 0))
    ranked = sorted(
        totals.items(), key=lambda kv: kv[1]["self_ms"], reverse=True
    )
    return [
        {
            "stage": stage,
            "function": function,
            "self_ms": round(entry["self_ms"], 3),
            "samples": entry["samples"],
        }
        for (stage, function), entry in ranked[:max_frames]
    ]


def analyze_trace(
    path: Any,
    since_ts: Optional[float] = None,
    until_ts: Optional[float] = None,
    window_index: Optional[Dict[str, Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """The full analysis document for one trace (a file path, or a list
    of sink bases to read-merge — the per-worker variants of one
    logical trace): span summaries, the request breakdown, the stream-
    session breakdown, the build-phase breakdown with each phase's parts
    and self time, and the aggregated profile — the JSON shape
    ``gordo-tpu trace --as-json``
    prints and the tests golden-check. ``since_ts``/``until_ts``
    restrict the analysis to a time window (``--since``/``--last``);
    ``window_index`` (``aggregate.sink_window_index``) lets rotated
    generations be skipped by recorded span window, not just mtime."""
    paths = [path] if isinstance(path, str) else list(path)
    spans = list(
        read_traces(
            paths,
            since_ts=since_ts,
            until_ts=until_ts,
            window_index=window_index,
        )
    )
    doc = {
        "trace": paths[0] if len(paths) == 1 else paths,
        "spans_read": len(spans),
        "span_summary": summarize_spans(spans),
        "request_breakdown": request_breakdown(spans),
        "stream_breakdown": stream_breakdown(spans),
        "build_breakdown": build_breakdown(spans),
        "prediction_accuracy": prediction_accuracy(spans),
        "profile_frames": top_profile_frames(spans),
    }
    if since_ts is not None or until_ts is not None:
        doc["window"] = {"since_ts": since_ts, "until_ts": until_ts}
    return doc


# -- rendering ---------------------------------------------------------------


def _table(rows: List[List[str]], header: List[str]) -> str:
    widths = [
        max(len(str(row[i])) for row in [header] + rows)
        for i in range(len(header))
    ]
    lines = [
        "  ".join(str(cell).ljust(widths[i]) for i, cell in enumerate(row))
        for row in [header, ["-" * w for w in widths]] + rows
    ]
    return "\n".join(line.rstrip() for line in lines)


def render_analysis(doc: Dict[str, Any]) -> str:
    """Human-readable rendering of :func:`analyze_trace`'s document."""
    trace = doc["trace"]
    if isinstance(trace, list):
        trace = ", ".join(trace)
    out: List[str] = [f"trace: {trace}  ({doc['spans_read']} spans)"]
    window = doc.get("window")
    if window:
        out.append(
            f"window: since_ts={window.get('since_ts')} "
            f"until_ts={window.get('until_ts')}"
        )

    summary = doc.get("span_summary") or {}
    if summary:
        out.append("\nSpan latency (ms):")
        out.append(
            _table(
                [
                    [
                        name,
                        dist["count"],
                        dist["p50_ms"],
                        dist["p95_ms"],
                        dist["p99_ms"],
                    ]
                    for name, dist in summary.items()
                ],
                ["span", "count", "p50", "p95", "p99"],
            )
        )

    breakdown = doc.get("request_breakdown")
    if breakdown:
        out.append(
            f"\nRequests: {breakdown['requests']}  "
            f"walltime p50={breakdown['walltime_p50_ms']}ms "
            f"p95={breakdown['walltime_p95_ms']}ms "
            f"p99={breakdown['walltime_p99_ms']}ms"
        )
        out.append("\nPer-stage breakdown:")
        out.append(
            _table(
                [
                    [
                        name,
                        dist["p50_ms"],
                        dist["p95_ms"],
                        f"{dist['share_of_p50'] * 100:.1f}%",
                    ]
                    for name, dist in breakdown["stages"].items()
                ],
                ["stage", "p50", "p95", "share of p50"],
            )
        )
        coverage = breakdown["attribution_coverage"]
        out.append(
            f"\nattribution coverage: {coverage * 100:.1f}% of median "
            "request walltime explained by instrumented stages"
        )
        if breakdown["critical_path"]:
            path_text = "  >  ".join(
                f"{step['stage']} {step['duration_ms']}ms"
                for step in breakdown["critical_path"]
            )
            out.append(f"critical path (median request): {path_text}")

    stream = doc.get("stream_breakdown")
    if stream:
        totals = stream.get("totals") or {}
        out.append(
            f"\nStream sessions: {len(stream.get('streams') or {})}  "
            f"flushes={totals.get('flushes', 0)} "
            f"rows in={totals.get('rows_in', 0)} "
            f"scored={totals.get('rows_scored', 0)} "
            f"failed={totals.get('rows_failed', 0)} "
            f"shed={totals.get('rows_shed', 0)}"
        )
        out.append(
            _table(
                [
                    [
                        stream_id,
                        entry["flushes"],
                        entry["rows_scored"],
                        entry["lag_p50_ms"],
                        entry["lag_max_ms"],
                        entry["device_p50_ms"],
                        (
                            entry["predicted_device_p50_ms"]
                            if entry["predicted_device_p50_ms"] is not None
                            else "-"
                        ),
                        entry["linked_ingests"],
                    ]
                    for stream_id, entry in (
                        stream.get("streams") or {}
                    ).items()
                ],
                [
                    "stream",
                    "flushes",
                    "rows",
                    "lag p50",
                    "lag max",
                    "device p50",
                    "pred p50",
                    "links",
                ],
            )
        )
        for stream_id, entry in (stream.get("streams") or {}).items():
            if entry.get("critical_path"):
                path_text = "  >  ".join(
                    f"{step['stage']} {step['p50_ms']}ms"
                    for step in entry["critical_path"]
                )
                out.append(
                    f"critical path ({stream_id}, median): {path_text}"
                )

    build = doc.get("build_breakdown")
    if build:
        from .progress import cores_busy_text, latent_text, part_rates_text, scan_text

        out.append(
            "\nBuild phases (seconds; self = wall time no part covers; "
            "parts in thread-seconds; cpu = the share of a part's seconds "
            "its threads computed):"
        )
        rows: List[List[Any]] = []
        for phase, entry in build["phases"].items():
            rows.append(
                [
                    phase, entry["entries"], entry["seconds"], entry["self_seconds"],
                    cores_busy_text(entry).strip(),
                ]
            )
            for part, measured in entry["parts"].items():
                counters = [
                    f"{key}={measured[key]}"
                    for key in (
                        "validation_slots", "shuffle_columns",
                        "members", "params_resident_members", "selection_blocks_searched",
                    )
                    if key in measured
                ]
                if measured.get("gate_total"):
                    share = 100.0 * measured["gate_active"] / measured["gate_total"]
                    counters.append(f"gate_active_pct={share:.1f}")
                if measured.get("kv_lora_rank"):
                    counters.append(latent_text(measured))
                if measured.get("ssm_inner"):
                    counters.append(scan_text(measured))
                if counters:
                    part += f" [{', '.join(counters)}]"
                rows.append(
                    [
                        f"  {part}", measured["count"], measured["seconds"], "",
                        part_rates_text(measured).strip(),
                    ]
                )
        out.append(
            _table(rows, ["phase / part", "count", "seconds", "self", "cpu, rate"])
        )
        if build.get("compile"):
            out.append(
                "compile path: "
                + ", ".join(f"{k}={v}" for k, v in build["compile"].items())
            )

    accuracy = doc.get("prediction_accuracy")
    if accuracy:
        out.append("\nPrediction accuracy (cost model vs measured device ms):")
        out.append(
            _table(
                [
                    [
                        program,
                        entry["count"],
                        f"{entry['error_p50'] * 100:.1f}%",
                        f"{entry['error_p95'] * 100:.1f}%",
                        entry["bias"],
                    ]
                    for program, entry in accuracy.items()
                ],
                ["program", "pairs", "err p50", "err p95", "bias"],
            )
        )

    frames = doc.get("profile_frames") or []
    if frames:
        out.append("\nTop self-time frames (sampling profiler):")
        out.append(
            _table(
                [
                    [f["stage"], f["function"], f["self_ms"], f["samples"]]
                    for f in frames[:15]
                ],
                ["stage", "function", "self ms", "samples"],
            )
        )
    return "\n".join(out)
