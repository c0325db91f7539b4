"""
The ``fleet-health`` route (PR 9): the joined fleet-status document over
the served collection, and the serving-side health-ledger feed
(per-machine request/error counts from the prediction routes, residual
means from the fleet route).
"""

import json
import os

import pytest

from gordo_tpu.telemetry.fleet_health import (
    FLEET_HEALTH_FILE,
    ledger_for,
    reset_ledgers,
)

# Must match tests/server/conftest.py
PROJECT = "test-project"
REVISION = "1602324482000"

pytestmark = [pytest.mark.fleet_health, pytest.mark.observability]


def url(rest: str) -> str:
    return f"/gordo/v0/{PROJECT}/{rest}"


def _remove_snapshots(collection_dir):
    """Every health-ledger dropping in the (session-scoped) collection
    dir: the snapshot, its per-worker variants and its shard directory.
    Whichever server test file ran before on this xdist worker may have
    left one, and a new ledger restores from what it finds."""
    import shutil

    stem = os.path.splitext(FLEET_HEALTH_FILE)[0]
    for name in os.listdir(collection_dir):
        if name.startswith(stem):
            path = os.path.join(collection_dir, name)
            shutil.rmtree(path) if os.path.isdir(path) else os.remove(path)


@pytest.fixture(autouse=True)
def _fresh_ledgers(collection_dir):
    reset_ledgers()
    _remove_snapshots(collection_dir)
    yield
    reset_ledgers()
    # the collection dir is session-scoped; snapshots must not leak
    # into later tests (e.g. model listings)
    _remove_snapshots(collection_dir)


def test_fleet_health_route_serves_joined_document(client, collection_dir):
    ledger = ledger_for(collection_dir)
    ledger.record_request("machine-1")
    ledger.record_drift(
        "machine-1", True, ["feature-shift tag-1 (3.00σ)"],
        {"feature_shift_max": 3.0},
    )

    resp = client.get(url("fleet-health"))
    assert resp.status_code == 200
    doc = resp.json
    assert doc["directory"] == os.path.normpath(collection_dir)
    assert doc["revision"] == REVISION
    # the live in-process ledger answers, snapshot or not
    summary = doc["health"]["summary"]
    assert summary["machines"] == 1
    assert summary["drifting"] == 1
    machine = doc["health"]["machines"]["machine-1"]
    assert machine["health"]["state"] == "drifting"
    assert machine["drift"]["reasons"] == ["feature-shift tag-1 (3.00σ)"]
    # device + program sections always present (may be degraded)
    assert "compile_cache" in doc["device"]
    assert set(doc["programs"]) == {
        "programs", "signatures", "by_precision", "by_backend"
    }
    # missing sections are null, not errors
    assert doc["build"] is None
    assert doc["lifecycle"] is None


@pytest.mark.scale
def test_fleet_health_route_machine_selection_params(client, collection_dir):
    """The bounded-surface query grammar (PR 16): ``?machines=`` selects
    records explicitly, ``?limit=``/``?offset=`` page the selection."""
    ledger = ledger_for(collection_dir)
    names = [f"route-m-{i:02d}" for i in range(12)]
    for name in names:
        ledger.record_request(name)

    doc = client.get(url("fleet-health?machines=none")).json
    assert doc["health"]["machines"] is None
    assert doc["health"]["machines_total"] == 12
    assert doc["health"]["machines_truncated"] is True
    assert doc["health"]["summary"]["machines"] == 12

    doc = client.get(url("fleet-health?machines=all&limit=5")).json
    assert sorted(doc["health"]["machines"]) == names[:5]
    assert doc["health"]["machines_offset"] == 0
    assert doc["health"]["machines_truncated"] is True

    doc = client.get(url("fleet-health?machines=all&limit=5&offset=10")).json
    assert sorted(doc["health"]["machines"]) == names[10:]
    assert doc["health"]["machines_truncated"] is False

    doc = client.get(
        url("fleet-health?machines=route-m-03,route-m-07,no-such")
    ).json
    assert sorted(doc["health"]["machines"]) == ["route-m-03", "route-m-07"]

    # malformed paging never errors — it falls back to defaults
    doc = client.get(url("fleet-health?machines=all&limit=zap&offset=zap")).json
    assert len(doc["health"]["machines"]) == 12


def test_fleet_health_route_without_any_data_still_answers(client):
    resp = client.get(url("fleet-health"))
    assert resp.status_code == 200
    assert resp.json["health"] is None


def test_prediction_requests_feed_the_ledger(
    client, collection_dir, sensor_payload
):
    resp = client.post(
        url("machine-1/prediction"),
        data=json.dumps(sensor_payload),
        content_type="application/json",
    )
    assert resp.status_code == 200
    ledger = ledger_for(collection_dir)
    machine = ledger.machine("machine-1")
    assert machine["serving"]["requests"] == 1
    assert machine["serving"]["errors"] == 0
    # a metadata GET is not scoring traffic — it must not count
    assert client.get(url("machine-1/metadata")).status_code == 200
    assert ledger.machine("machine-1")["serving"]["requests"] == 1


def test_unknown_model_names_never_mint_ledger_records(
    client, collection_dir
):
    """gordo_name is client-supplied URL text: a scanner hitting random
    model paths must not grow the ledger (the request-derived-identity
    cardinality class, moved from labels into the ledger)."""
    for name in ("no-such-model", "also-missing"):
        resp = client.post(
            url(f"{name}/prediction"),
            data=json.dumps({"X": {}}),
            content_type="application/json",
        )
        assert resp.status_code >= 400
    ledger = ledger_for(collection_dir)
    assert ledger.machine("no-such-model") is None
    assert ledger.machine("also-missing") is None
    assert ledger.summary()["machines"] == 0


def test_client_errors_do_not_mark_the_machine(client, collection_dir):
    resp = client.post(
        url("machine-1/prediction"),
        data=json.dumps({"X": {"wrong": {"2020-01-01T00:00:00+00:00": 1.0}}}),
        content_type="application/json",
    )
    assert 400 <= resp.status_code < 500
    machine = ledger_for(collection_dir).machine("machine-1")
    assert machine["serving"]["requests"] == 1
    assert machine["serving"]["errors"] == 0
    assert machine["health"]["state"] == "healthy"


def test_fleet_route_records_residual_means(
    client, collection_dir, sensor_payload
):
    resp = client.post(
        url("prediction/fleet"),
        data=json.dumps({"X": {"machine-1": sensor_payload["X"]}}),
        content_type="application/json",
    )
    assert resp.status_code == 200
    assert "machine-1" in resp.json["data"]
    machine = ledger_for(collection_dir).machine("machine-1")
    assert machine["serving"]["requests"] == 1
    assert machine["serving"]["rows"] > 0
    assert machine["serving"]["residual_mean"] is not None
    assert machine["serving"]["residual_mean"] >= 0.0


def test_health_switch_off_keeps_routes_clean(
    client, collection_dir, sensor_payload, monkeypatch
):
    monkeypatch.setenv("GORDO_TPU_FLEET_HEALTH", "0")
    resp = client.post(
        url("machine-1/prediction"),
        data=json.dumps(sensor_payload),
        content_type="application/json",
    )
    assert resp.status_code == 200
    assert not os.path.exists(os.path.join(collection_dir, FLEET_HEALTH_FILE))
    # the route still answers — health section simply null
    assert client.get(url("fleet-health")).status_code == 200
