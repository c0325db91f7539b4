"""
Route-level chaos drills: concurrent WSGI clients + injected device
faults against one member of a coalesced fleet. The contract under
test is the PR's acceptance criterion — innocent riders see ZERO 5xx,
the poison member walks the documented error ladder (500 isolated →
503 + Retry-After quarantined → 200 after the half-open probe), the
health ledger narrates the trip/recovery, and a hot-swap mid-drill
drops nothing.
"""

import json
import os
import threading
import time

import numpy as np
import pytest
from werkzeug.test import Client

from gordo_tpu import telemetry
from gordo_tpu.server import build_app
from gordo_tpu.server.fleet_store import STORE
from gordo_tpu.telemetry.fleet_health import (
    breaker_tripped_machines,
    reset_ledgers,
)
from gordo_tpu.utils.faults import FaultRule, InjectedDeviceError, inject

from tests.serve.conftest import (
    BATCH_NAMES,
    PROJECT,
    installed_engine,
    run_threads,
    temp_env_vars,
    tiny_config,
    warm_store,
)

pytestmark = [pytest.mark.serve, pytest.mark.chaos]

POISON = "batch-a"
INNOCENTS = [n for n in BATCH_NAMES if n != POISON]


@pytest.fixture
def clean_ledgers(serve_collection_dir):
    """Ledger snapshots land in the session-scoped collection dir; drop
    the in-process registry and the files so drills stay independent."""
    reset_ledgers()
    yield
    reset_ledgers()
    for entry in list(os.listdir(serve_collection_dir)):
        if entry.startswith("fleet_health"):
            os.remove(os.path.join(serve_collection_dir, entry))


def post(app, name, payload):
    return Client(app).post(
        f"/gordo/v0/{PROJECT}/{name}/prediction", json=payload
    )


def poison_record(collection_dir):
    return telemetry.ledger_for(collection_dir).document()["machines"][POISON]


def test_chaos_drill_innocents_zero_5xx_breaker_trips_and_recovers(
    serve_collection_dir, batch_payload, clean_ledgers
):
    with temp_env_vars(
        MODEL_COLLECTION_DIR=serve_collection_dir,
        GORDO_TPU_SERVE_WARMUP="0",
        GORDO_TPU_BREAKER_THRESHOLD="2",
        GORDO_TPU_BREAKER_COOLDOWN_S="0.4",
        GORDO_TPU_BREAKER_MAX_COOLDOWN_S="3",
        GORDO_TPU_HEALTH_HEARTBEAT="0",
    ):
        app = build_app(config={"EXPECTED_MODELS": BATCH_NAMES})
        with installed_engine(tiny_config(max_delay_ms=60.0)) as engine:
            warm_store(serve_collection_dir)
            statuses = {name: [] for name in BATCH_NAMES}
            lock = threading.Lock()
            stop = threading.Event()

            def hammer(i):
                # 8 concurrent route-level clients over the whole fleet
                name = BATCH_NAMES[i % len(BATCH_NAMES)]
                while not stop.is_set():
                    resp = post(app, name, batch_payload)
                    with lock:
                        statuses[name].append(resp.status_code)

            rule = FaultRule(
                "serve_device_program",
                match=f"*:f32:{POISON}",
                times=None,
                exc=InjectedDeviceError,
            )
            threads = [
                threading.Thread(target=hammer, args=(i,), daemon=True)
                for i in range(8)
            ]
            with inject(rule):
                for thread in threads:
                    thread.start()
                # two seconds of it, and for as long as a loaded host
                # takes to walk the poison member up its ladder
                threading.Event().wait(2.0)
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    with lock:
                        if {500, 503} <= set(statuses[POISON]):
                            break
                    time.sleep(0.05)
                stop.set()
                for thread in threads:
                    thread.join(timeout=30)

                # the containment contract: innocent riders NEVER 5xx
                for name in INNOCENTS:
                    codes = statuses[name]
                    assert codes, f"no traffic reached {name}"
                    assert all(c == 200 for c in codes), {
                        name: sorted(set(codes))
                    }
                # the poison member walked the ladder: isolated 500s,
                # then the breaker's 503 quarantine
                poison_codes = set(statuses[POISON])
                assert 500 in poison_codes
                assert 503 in poison_codes
                assert not poison_codes - {500, 503}
                stats = engine.stats()
                assert stats["breaker_trips"] >= 1
                # the fault still fires and no client is left: only a
                # request moves the breaker, and a probe can only fail
                assert stats["breaker"]["open"] == 1

                # 503 carries Retry-After derived from the breaker
                # backoff. Quarantine 503s are backpressure, not fresh
                # error marks: the error count stops growing once the
                # breaker is open. A request that finds the cool-down
                # run out on a loaded host is the half-open probe: it
                # fails (500), the breaker re-opens for longer (3 s at
                # the most here), and the next request is asked instead.
                for _ in range(12):
                    errors_now = poison_record(serve_collection_dir)["serving"]["errors"]
                    resp = post(app, POISON, batch_payload)
                    if resp.status_code == 503:
                        break
                    assert resp.status_code == 500
                assert resp.status_code == 503
                assert int(resp.headers["Retry-After"]) >= 1
                assert "quarantined" in json.loads(resp.data)["error"]
                record = poison_record(serve_collection_dir)
                assert record["serving"]["errors"] == errors_now

                # the ledger narrated the trip (what the lifecycle
                # supervisor reads to nominate a rebuild)
                assert engine.stats()["breaker"]["open"] == 1
                assert record["breaker"]["state"] == "open"
                assert record["breaker"]["trips"] >= 1
                assert record["health"]["state"] == "quarantined"
                assert POISON in breaker_tripped_machines(serve_collection_dir)

            # recovery: faults stopped with the inject() exit; after the
            # cooldown the half-open probe scores and the member serves.
            # The wait is for the breaker, however long the host takes.
            deadline = time.monotonic() + 30.0
            while True:
                resp = post(app, POISON, batch_payload)
                if resp.status_code == 200 or time.monotonic() > deadline:
                    break
                assert resp.status_code == 503, resp.data
                time.sleep(0.05)
            assert resp.status_code == 200, resp.data
            assert engine.stats()["breaker"]["open"] == 0
            assert poison_record(serve_collection_dir)["breaker"]["state"] == "closed"
            assert breaker_tripped_machines(serve_collection_dir) == {}


def test_hot_swap_mid_faults_drops_nothing_for_innocents(
    serve_collection_dir, batch_payload, clean_ledgers, tmp_path
):
    """A lifecycle hot-swap while device faults are firing: innocent
    riders still see zero 5xx across the swap, and the swapped-in
    revision starts with a clean breaker slate."""
    from gordo_tpu.lifecycle import publish_canary

    root = os.path.dirname(serve_collection_dir)
    base_revision = os.path.basename(serve_collection_dir)
    alt_dir = publish_canary(
        root, base_revision, serve_collection_dir, [], "9900000000001"
    )
    try:
        with temp_env_vars(
            MODEL_COLLECTION_DIR=serve_collection_dir,
            GORDO_TPU_SERVE_WARMUP="0",
            GORDO_TPU_BREAKER_THRESHOLD="2",
            GORDO_TPU_BREAKER_COOLDOWN_S="60",
        ):
            app = build_app(config={"EXPECTED_MODELS": BATCH_NAMES})
            with installed_engine(tiny_config(max_delay_ms=60.0)) as engine:
                warm_store(serve_collection_dir)
                codes = {name: [] for name in BATCH_NAMES}
                lock = threading.Lock()
                stop = threading.Event()

                def hammer(i):
                    name = BATCH_NAMES[i % len(BATCH_NAMES)]
                    while not stop.is_set():
                        resp = post(app, name, batch_payload)
                        with lock:
                            codes[name].append(resp.status_code)

                rule = FaultRule(
                    "serve_device_program",
                    match=f"*:f32:{POISON}",
                    times=None,
                    exc=InjectedDeviceError,
                )
                threads = [
                    threading.Thread(target=hammer, args=(i,), daemon=True)
                    for i in range(8)
                ]
                with inject(rule):
                    for thread in threads:
                        thread.start()
                    threading.Event().wait(0.8)
                    STORE.swap(serve_collection_dir, alt_dir, warm=True)
                    threading.Event().wait(0.8)
                    stop.set()
                    for thread in threads:
                        thread.join(timeout=30)
                    for name in INNOCENTS:
                        assert codes[name]
                        assert all(c == 200 for c in codes[name]), {
                            name: sorted(set(codes[name]))
                        }
                    # the swap minted a new RevisionFleet: the poison
                    # member's breaker restarted closed (and the still-
                    # firing fault begins tripping it fresh)
                    poison_codes = set(codes[POISON])
                    assert poison_codes <= {200, 500, 503}
    finally:
        STORE.clear()


def test_batched_and_unbatched_error_contract_table(
    serve_collection_dir, batch_payload, clean_ledgers
):
    """The documented 4xx/5xx ladder stays intact around containment:
    malformed client payloads keep answering 400 even while a breaker
    is open for another member."""
    with temp_env_vars(
        MODEL_COLLECTION_DIR=serve_collection_dir,
        GORDO_TPU_SERVE_WARMUP="0",
        GORDO_TPU_BREAKER_THRESHOLD="1",
        GORDO_TPU_BREAKER_COOLDOWN_S="60",
    ):
        app = build_app(config={"EXPECTED_MODELS": BATCH_NAMES})
        with installed_engine(tiny_config(max_delay_ms=30.0)) as engine:
            warm_store(serve_collection_dir)
            rule = FaultRule(
                "serve_device_program",
                match=f"*:f32:{POISON}",
                times=None,
                exc=InjectedDeviceError,
            )
            with inject(rule):
                assert post(app, POISON, batch_payload).status_code == 500
            assert post(app, POISON, batch_payload).status_code == 503
            # a malformed body on an INNOCENT member: still the client's
            # 400, untouched by the quarantine next door
            bad = Client(app).post(
                f"/gordo/v0/{PROJECT}/batch-b/prediction",
                json={"X": {"tag-1": {"2020-01-01T00:00:00": "not-a-number"}}},
            )
            assert bad.status_code == 400
            ok = post(app, "batch-b", batch_payload)
            assert ok.status_code == 200
            assert isinstance(
                json.loads(ok.data)["data"]["model-output"], dict
            )
