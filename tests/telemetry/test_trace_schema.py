"""Golden-schema contract for the JSONL span traces.

Downstream consumers — cost-model calibration (``planner.calibrate``),
``gordo-tpu trace``, the Prometheus span listener, external OTLP
shippers — parse these dicts by field name. A rename or type change
must fail HERE, not in a consumer three PRs later. The schema below is
the wire contract; extending it (new optional fields) is fine, breaking
it is a conscious decision that updates this file.
"""

import json

import pytest

from gordo_tpu.telemetry import SpanRecorder

pytestmark = pytest.mark.observability

#: required fields and types of EVERY span in build_trace.jsonl /
#: serve_trace.jsonl (the SpanRecorder wire shape)
SPAN_SCHEMA = {
    "name": str,
    "context": dict,
    "parent_id": (str, type(None)),
    "kind": str,
    "start_time": str,
    "end_time": str,
    "duration_ms": (int, float),
    "status": dict,
    "attributes": dict,
    "resource": dict,
}

CONTEXT_SCHEMA = {"trace_id": str, "span_id": str}

#: optional fields, checked when present
LINK_SCHEMA = {"context": dict}


def assert_span_schema(span: dict):
    for field, types in SPAN_SCHEMA.items():
        assert field in span, f"span missing {field!r}: {span}"
        assert isinstance(span[field], types), (field, span[field])
    for field, types in CONTEXT_SCHEMA.items():
        assert isinstance(span["context"][field], types)
    assert len(span["context"]["trace_id"]) == 32
    assert len(span["context"]["span_id"]) == 16
    assert span["status"]["status_code"] in ("OK", "ERROR")
    assert span["kind"] in ("internal", "event", "server")
    assert span["resource"]["service.name"]
    json.dumps(span)  # wire-serializable, always
    for link in span.get("links", []):
        assert isinstance(link["context"]["trace_id"], str)
        assert isinstance(link["context"]["span_id"], str)


def test_recorded_span_schema(tmp_path):
    sink = tmp_path / "t.jsonl"
    rec = SpanRecorder(sink_path=str(sink), retain_spans=True)
    with rec.span("device_program", program="fit", compile=True):
        pass
    with rec.span("serve_batch", size=3) as handle:
        handle.link("a" * 32, "b" * 16, name="m-1", queue_wait_ms=0.5)
    rec.event("machine_built", machine="m-1")
    rec.record("queue_wait", 0.003)
    rec.close()
    lines = [json.loads(l) for l in sink.read_text().splitlines()]
    assert len(lines) == 4
    for span in lines:
        assert_span_schema(span)
    batch = next(s for s in lines if s["name"] == "serve_batch")
    assert batch["links"][0]["attributes"]["name"] == "m-1"
    event = next(s for s in lines if s["kind"] == "event")
    assert event["duration_ms"] == 0


def test_error_span_schema():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("doomed"):
            raise RuntimeError("boom")
    (span,) = rec.finished()
    assert_span_schema(span)
    assert span["status"]["status_code"] == "ERROR"
    assert "boom" in span["status"]["description"]


def test_exported_request_trace_schema(tmp_path, monkeypatch):
    """The serving-side export path: request root span (kind=server),
    nested stage spans, and the profile span — the exact shapes
    ``gordo-tpu trace`` and the route bench consume."""
    from gordo_tpu import telemetry
    from gordo_tpu.telemetry import serving

    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "1")
    monkeypatch.setenv(telemetry.TRACE_DIR_ENV, str(tmp_path))
    serving.reset_serve_recorder()
    try:
        trace_id, span_id = "c" * 32, "d" * 16
        timing = SpanRecorder(service="gordo-tpu-server", trace_id=trace_id)
        timing.default_parent_id = span_id
        with timing.span("inference"):
            pass
        serving.export_request_trace(
            timing,
            span_id=span_id,
            parent_id="e" * 16,
            start=1_700_000_000.0,
            duration_s=0.25,
            attributes={
                "http.method": "POST",
                "http.route": "prediction",
                "http.status_code": 200,
                "gordo_name": "m-1",
                "revision": "123",
            },
            profile={
                "samples": 10,
                "interval_ms": 5.0,
                "duration_ms": 50.0,
                "frames": [
                    {
                        "stage": "inference",
                        "function": "x.py:f",
                        "samples": 9,
                        "self_ms": 45.0,
                    }
                ],
            },
        )
        recorder = serving.serve_recorder()
        recorder.flush()
        lines = [
            json.loads(l)
            for l in open(serving.serve_trace_path()).read().splitlines()
        ]
        by_name = {s["name"]: s for s in lines}
        assert set(by_name) == {"inference", "request", "profile"}
        for span in lines:
            assert_span_schema(span)
            assert span["context"]["trace_id"] == trace_id
        request = by_name["request"]
        assert request["kind"] == "server"
        assert request["context"]["span_id"] == span_id
        assert request["parent_id"] == "e" * 16
        assert request["duration_ms"] == 250.0
        assert request["attributes"]["http.status_code"] == 200
        # stage + profile spans nest under the request span
        assert by_name["inference"]["parent_id"] == span_id
        assert by_name["profile"]["parent_id"] == span_id
        assert by_name["profile"]["attributes"]["frames"][0]["self_ms"] == 45.0
    finally:
        serving.reset_serve_recorder()


@pytest.mark.stream
def test_stream_span_schema(tmp_path, monkeypatch):
    """The streaming plane's span vocabulary, golden-checked end to end:
    one ``stream_ingest`` per ingest POST, one enriched ``stream_score``
    per watermark flush (row accounting split, freshness lag numbers,
    the compact rows-weighted ``lag_hist``, predicted vs measured device
    time, and OTel links back to the drained ingests), and one
    ``stream_emit`` per event fan-out."""
    import numpy as np
    import pandas as pd

    from gordo_tpu import serve, telemetry
    from gordo_tpu.server.fleet_store import STORE
    from gordo_tpu.stream import (
        StreamConfig,
        StreamPlane,
        reset_stream_telemetry,
    )
    from gordo_tpu.telemetry import serving

    class EchoFleet:
        def model(self, name):
            return object()

        def loaded_specs(self):
            return {}

        def fleet_scores(self, inputs):
            return (
                {
                    name: (
                        np.zeros((len(X), 2)),
                        np.full(len(X), 0.5),
                    )
                    for name, X in inputs.items()
                },
                {},
            )

    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "1")
    monkeypatch.setenv(telemetry.TRACE_DIR_ENV, str(tmp_path))
    fleet = EchoFleet()
    monkeypatch.setattr(STORE, "route", lambda directory: directory)
    monkeypatch.setattr(STORE, "fleet", lambda directory: fleet)
    engine = serve.get_engine()
    serve.install_engine(None)
    serve.reset_stream_breakers()
    serving.reset_serve_recorder()
    reset_stream_telemetry()
    try:
        plane = StreamPlane(
            StreamConfig(
                ring_rows=16,
                window_rows=4,
                outbox_events=32,
                session_ttl_s=60.0,
                heartbeat_s=0.05,
                max_sessions=2,
                shed_retry_s=0.5,
            )
        )
        session = plane.session("p", "s1", str(tmp_path / "rev-a"))
        plane.ingest(
            session,
            {
                "m-1": pd.DataFrame({"t": [float(i) for i in range(4)]}),
                "m-2": pd.DataFrame({"t": [float(i) for i in range(4)]}),
            },
        )
        serving.serve_recorder().flush()
        lines = [
            json.loads(l)
            for l in open(serving.serve_trace_path()).read().splitlines()
        ]
        by_name = {s["name"]: s for s in lines}
        assert {"stream_ingest", "stream_score", "stream_emit"} <= set(
            by_name
        )
        for span in lines:
            assert_span_schema(span)
        ingest = by_name["stream_ingest"]
        assert ingest["attributes"]["stream"] == "s1"
        assert ingest["attributes"]["machines"] == 2
        assert ingest["attributes"]["rows"] == 8
        assert ingest["attributes"]["shed"] == 0
        assert ingest["attributes"]["errors"] == 0
        score = by_name["stream_score"]
        attrs = score["attributes"]
        assert attrs["stream"] == "s1"
        assert attrs["rows"] == 8
        assert attrs["rows_scored"] == 8
        assert attrs["rows_failed"] == 0
        assert attrs["windows"] == 2
        assert attrs["shed"] == 0
        assert attrs["revision"] == "rev-a"
        assert attrs["lag_p50_ms"] >= 0.0
        assert attrs["lag_max_ms"] >= attrs["lag_p50_ms"]
        assert attrs["lag_sum_ms"] >= 0.0
        assert isinstance(attrs["lag_hist"], list)
        assert sum(attrs["lag_hist"]) == 8  # rows-weighted
        assert attrs["device_ms"] >= 0.0
        assert "predicted_device_ms" in attrs
        # the flush links back to the ingest exchange it drained
        linked = [
            link["context"]["span_id"] for link in score.get("links") or []
        ]
        assert ingest["context"]["span_id"] in linked
        emit = by_name["stream_emit"]
        assert emit["attributes"]["stream"] == "s1"
        assert emit["attributes"]["events"] == 2
        assert emit["attributes"]["machines"] == 2
    finally:
        serving.reset_serve_recorder()
        serve.reset_stream_breakers()
        serve.install_engine(engine)
        reset_stream_telemetry()
