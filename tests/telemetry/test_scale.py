"""
Fleet-scale observability suite (PR 16): the sharded health-ledger
layout (adaptive resharding, dirty-shard flushing, monolithic-snapshot
migration, crash-torn dual-layout merge), the rollup manifest's
counting-open read contract, manifest-window trace skipping, the
bounded fleet-status surface with explicit machine selection/paging,
and the O(unhealthy) breaker-board summary at 5k tracked members.

Corpora come from ``fleetgen.py`` beside this file, a deterministic
generator; the fleets are sized to stay inside the tier-1 budget while
still crossing every scale threshold (reshard trigger, inline cap).
"""

import json
import os
import time
from pathlib import Path

import pytest

from gordo_tpu.telemetry.aggregate import (
    ROLLUP_DIR,
    ROLLUP_MANIFEST_FILE,
    RollupStore,
    sink_window_index,
)
from gordo_tpu.telemetry.fleet_health import (
    FLEET_HEALTH_FILE,
    FLEET_HEALTH_SHARD_DIR,
    FLEET_HEALTH_SUMMARY_FILE,
    FleetHealthLedger,
    fleet_status_document,
    health_snapshot_units,
    ledger_for,
    load_health,
    load_merged_health,
    reset_ledgers,
)
from gordo_tpu.telemetry.trace_analysis import iter_trace_files

from tests.telemetry import fleetgen

pytestmark = [pytest.mark.scale, pytest.mark.observability]


@pytest.fixture(autouse=True)
def _fresh_registry():
    reset_ledgers()
    yield
    reset_ledgers()


def make_ledger(tmp_path, **kwargs) -> FleetHealthLedger:
    kwargs.setdefault("heartbeat_seconds", 0.0)
    return FleetHealthLedger(directory=str(tmp_path), **kwargs)


def shard_files(tmp_path):
    shard_dir = tmp_path / FLEET_HEALTH_SHARD_DIR
    if not shard_dir.is_dir():
        return []
    return sorted(
        entry
        for entry in os.listdir(shard_dir)
        if entry.startswith("shard-") and entry.endswith(".json")
    )


# -- shard layout -------------------------------------------------------------


def test_small_fleet_keeps_monolithic_snapshot(tmp_path):
    ledger = make_ledger(tmp_path)
    fleetgen.populate_ledger(ledger, fleetgen.machine_names(40))
    assert (tmp_path / FLEET_HEALTH_FILE).exists()
    assert not (tmp_path / FLEET_HEALTH_SHARD_DIR).exists()
    assert len(load_health(str(tmp_path))["machines"]) == 40


def test_adaptive_reshard_partitions_without_overlap(tmp_path):
    """Past the per-shard target the layout splits: every machine lands
    in exactly one shard file, the monolithic spelling is retired, and
    ``summary.json`` carries the bounded fold."""
    names = fleetgen.machine_names(1200)
    # a long heartbeat keeps throttled per-record writes out of the
    # test's way — only state transitions and the final flush persist
    ledger = make_ledger(tmp_path, heartbeat_seconds=3600.0)
    fleetgen.populate_ledger(ledger, names)

    # ceil(1200 / 512) = 3 -> next power of two = 4 shards
    files = shard_files(tmp_path)
    assert files == [f"shard-{i:03d}of004.json" for i in range(4)]
    assert not (tmp_path / FLEET_HEALTH_FILE).exists()

    seen = []
    for entry in files:
        doc = json.loads((tmp_path / FLEET_HEALTH_SHARD_DIR / entry).read_text())
        assert doc["kind"] == "fleet-health-shard"
        assert doc["shards"] == 4
        seen.extend(doc["machines"])
    assert len(seen) == len(set(seen)) == 1200  # a partition, not a cover
    assert sorted(seen) == names

    summary_doc = json.loads(
        (tmp_path / FLEET_HEALTH_SHARD_DIR / FLEET_HEALTH_SUMMARY_FILE).read_text()
    )
    assert summary_doc["machines_total"] == 1200
    assert summary_doc["summary"]["machines"] == 1200
    assert summary_doc["offenders"]  # drift/quarantine sprinkled by fleetgen


def test_pinned_shard_count_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("GORDO_TPU_HEALTH_SHARDS", "8")
    ledger = make_ledger(tmp_path)
    fleetgen.populate_ledger(ledger, fleetgen.machine_names(64))
    files = shard_files(tmp_path)
    assert files and all(entry.endswith("of008.json") for entry in files)


def test_dirty_flush_rewrites_only_the_owning_shard(tmp_path):
    """One machine's update costs one bounded shard file (plus the
    summary) — never a rewrite of the whole fleet. This is the contract
    the BENCH_SCALE ``ledger_dirty_flush_shard_ratio`` gate holds at
    10k members."""
    names = fleetgen.machine_names(1200)
    ledger = make_ledger(tmp_path, heartbeat_seconds=3600.0)
    fleetgen.populate_ledger(ledger, names)

    shard_dir = tmp_path / FLEET_HEALTH_SHARD_DIR
    before = {
        entry: (shard_dir / entry).read_bytes()
        for entry in os.listdir(shard_dir)
    }
    ledger.record_scores(names[0], rows=5, residual_mean=0.5, write=False)
    ledger.flush()
    after = {
        entry: (shard_dir / entry).read_bytes()
        for entry in os.listdir(shard_dir)
    }

    assert set(before) == set(after)
    changed = {entry for entry in after if after[entry] != before[entry]}
    owning = f"shard-{ledger._shard_of(names[0]):03d}of004.json"
    assert changed == {owning, FLEET_HEALTH_SUMMARY_FILE}


# -- migration ----------------------------------------------------------------


def test_monolithic_snapshot_migrates_and_is_never_reread(tmp_path, monkeypatch):
    """The legacy monolithic ``fleet_health.json`` is read ONCE at
    restore; the first sharded flush reshards it and retires the file.
    A poisoned legacy file planted afterwards must be invisible to
    every reader — the shard layout is authoritative."""
    names = fleetgen.machine_names(1200)
    monkeypatch.setenv("GORDO_TPU_HEALTH_SHARDS", "1")  # force old layout
    legacy = make_ledger(tmp_path, heartbeat_seconds=3600.0)
    fleetgen.populate_ledger(legacy, names)
    assert (tmp_path / FLEET_HEALTH_FILE).exists()
    assert not shard_files(tmp_path)
    monkeypatch.delenv("GORDO_TPU_HEALTH_SHARDS")
    reset_ledgers()

    ledger = ledger_for(str(tmp_path))
    assert ledger.machine_count() == 1200  # the one-time legacy read
    ledger.flush()
    assert len(shard_files(tmp_path)) == 4
    assert not (tmp_path / FLEET_HEALTH_FILE).exists()  # retired

    (tmp_path / FLEET_HEALTH_FILE).write_text(
        json.dumps(
            {
                "version": 1,
                "machines": {"poison-machine": {}},
                "summary": {"machines": 1},
            }
        )
    )
    reset_ledgers()
    restored = ledger_for(str(tmp_path))
    assert restored.machine_count() == 1200
    assert restored.machine("poison-machine") is None
    assert "poison-machine" not in load_health(str(tmp_path))["machines"]


def test_crash_torn_dual_layout_never_double_counts(tmp_path, monkeypatch):
    """A worker that crashed between the shard flush and the legacy
    unlink leaves BOTH layouts under one stem; it must count once, the
    shard directory winning."""
    names = fleetgen.machine_names(8)
    monkeypatch.setenv("GORDO_TPU_HEALTH_SHARDS", "4")
    ledger = make_ledger(tmp_path)
    for name in names:
        ledger.record_request(name)
    ledger.flush()
    monkeypatch.delenv("GORDO_TPU_HEALTH_SHARDS")

    # resurrect the legacy spelling with inflated counts
    stale = {
        "version": 1,
        "updated_at": "2099-01-01T00:00:00+00:00",
        "machines": {
            name: {"serving": {"requests": 100, "errors": 100, "rows": 0}}
            for name in names
        },
        "summary": {"machines": 8},
    }
    (tmp_path / FLEET_HEALTH_FILE).write_text(json.dumps(stale))

    units = health_snapshot_units(str(tmp_path))
    assert [unit["kind"] for unit in units] == ["shards"]

    reset_ledgers()
    merged = load_merged_health(str(tmp_path))
    assert merged["summary"]["machines"] == 8
    for name in names:
        assert merged["machines"][name]["serving"]["requests"] == 1


# -- bounded fleet-status -----------------------------------------------------


def test_fleet_status_bounds_past_inline_threshold(tmp_path, monkeypatch):
    monkeypatch.setenv("GORDO_TPU_FLEET_STATUS_MAX_MACHINES", "50")
    names = fleetgen.machine_names(120)
    ledger = ledger_for(str(tmp_path))
    fleetgen.populate_ledger(ledger, names)

    doc = fleet_status_document(str(tmp_path))
    health = doc["health"]
    assert health["machines"] is None
    assert health["machines_truncated"] is True
    assert health["machines_total"] == 120
    assert health["summary"]["machines"] == 120
    offenders = health["top_offenders"]
    assert 0 < len(offenders) <= 10
    assert all(o["state"] != "healthy" for o in offenders)


def test_fleet_status_explicit_selection_and_paging(tmp_path, monkeypatch):
    monkeypatch.setenv("GORDO_TPU_FLEET_STATUS_MAX_MACHINES", "50")
    names = fleetgen.machine_names(120)
    ledger = ledger_for(str(tmp_path))
    fleetgen.populate_ledger(ledger, names)

    paged = fleet_status_document(str(tmp_path), machines="all", limit=10)
    assert sorted(paged["health"]["machines"]) == names[:10]
    assert paged["health"]["machines_truncated"] is True
    assert paged["health"]["machines_offset"] == 0

    tail = fleet_status_document(
        str(tmp_path), machines="all", limit=10, offset=115
    )
    assert sorted(tail["health"]["machines"]) == names[115:]
    assert tail["health"]["machines_truncated"] is False

    # state filter: fleetgen quarantines every 503rd member (index 0)
    quarantined = fleet_status_document(
        str(tmp_path), machines="quarantined"
    )
    assert list(quarantined["health"]["machines"]) == [names[0]]

    picked = fleet_status_document(
        str(tmp_path), machines=f"{names[7]},{names[9]},no-such-machine"
    )
    assert sorted(picked["health"]["machines"]) == [names[7], names[9]]

    summary_only = fleet_status_document(str(tmp_path), machines="none")
    assert summary_only["health"]["machines"] is None
    assert summary_only["health"]["machines_total"] == 120


def test_fleet_status_page_limit_capped_by_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("GORDO_TPU_FLEET_STATUS_MAX_MACHINES", "20")
    names = fleetgen.machine_names(60)
    ledger = ledger_for(str(tmp_path))
    for name in names:
        ledger.record_request(name)
    ledger.flush()
    doc = fleet_status_document(str(tmp_path), machines="all", limit=10_000)
    assert len(doc["health"]["machines"]) == 20  # one page never exceeds it
    assert doc["health"]["machines_truncated"] is True


# -- rollup manifest ----------------------------------------------------------


def _span_corpus(tmp_path):
    names = fleetgen.machine_names(16)
    fleetgen.write_span_corpus(str(tmp_path), 2000, names, windows=8)
    RollupStore(str(tmp_path), seconds=60).aggregate()


def test_merged_rollup_opens_only_manifest_selected_files(tmp_path):
    """The counting-open contract BENCH_SCALE gates at 10k members: a
    bounded-window read opens the manifest plus exactly the overlapping
    window files — never a directory walk over every rotation."""
    _span_corpus(tmp_path)
    since = fleetgen.EPOCH + 60
    until = fleetgen.EPOCH + 180

    reader = RollupStore(str(tmp_path), seconds=60)  # no in-memory manifest
    opened = []
    original = reader._load_json

    def counting(path):
        opened.append(os.path.basename(path))
        return original(path)

    reader._load_json = counting
    doc = reader.merged(since=since, until=until)

    grid = range(
        (int(fleetgen.EPOCH) // 60 - 2) * 60, int(until) + 120, 60
    )
    selected = [s for s in grid if s + 60 > since and s < until]
    assert doc["window"]["merged_windows"] == len(selected)
    assert opened.count(ROLLUP_MANIFEST_FILE) == 1
    window_files = [n for n in opened if n != ROLLUP_MANIFEST_FILE]
    assert sorted(window_files) == sorted(f"{s}.json" for s in selected)


def test_manifest_tracks_sink_span_windows(tmp_path):
    _span_corpus(tmp_path)
    index = sink_window_index(str(tmp_path))
    entry = index["serve_trace.jsonl"]
    assert entry["complete"] is True
    assert fleetgen.EPOCH <= float(entry["min_ts"]) <= float(entry["max_ts"])


def test_rollup_reader_falls_back_without_usable_manifest(
    tmp_path, monkeypatch
):
    """No manifest trust (switch off, or a seconds-mismatched doc from
    another store generation) -> the directory walk answers, with
    identical results."""
    _span_corpus(tmp_path)
    since = fleetgen.EPOCH + 60
    until = fleetgen.EPOCH + 180
    baseline = RollupStore(str(tmp_path), seconds=60).merged(
        since=since, until=until
    )
    assert baseline["window"]["merged_windows"] > 0

    monkeypatch.setenv("GORDO_TPU_ROLLUP_MANIFEST", "0")
    walked = RollupStore(str(tmp_path), seconds=60).merged(
        since=since, until=until
    )
    assert walked == baseline
    monkeypatch.delenv("GORDO_TPU_ROLLUP_MANIFEST")

    manifest_path = tmp_path / ROLLUP_DIR / ROLLUP_MANIFEST_FILE
    doc = json.loads(manifest_path.read_text())
    doc["seconds"] = 999
    manifest_path.write_text(json.dumps(doc))
    stale = RollupStore(str(tmp_path), seconds=60).merged(
        since=since, until=until
    )
    assert stale == baseline


# -- trace window skipping ----------------------------------------------------


def test_trace_since_skips_rotated_generations_by_recorded_window(tmp_path):
    """``gordo-tpu trace --since`` trusts the manifest's recorded span
    windows over filesystem mtimes, in BOTH directions: a recently
    touched generation of ancient spans is skipped; an old-mtime file
    whose spans overlap the cutoff is read."""
    base = tmp_path / "serve_trace.jsonl"
    gen2 = tmp_path / "serve_trace.jsonl.2"  # oldest generation
    gen1 = tmp_path / "serve_trace.jsonl.1"
    for path in (gen2, gen1, base):
        path.write_text("")
    now = time.time()
    since = now - 3600.0
    os.utime(gen2, (now, now))  # mtime lies fresh; spans are ancient
    os.utime(gen1, (1.0, 1.0))  # mtime lies ancient; spans overlap

    index = {
        gen2.name: {"min_ts": 0.0, "max_ts": since - 100.0, "complete": True},
        gen1.name: {
            "min_ts": since + 10.0,
            "max_ts": since + 50.0,
            "complete": True,
        },
    }
    kept = iter_trace_files(str(base), since_ts=since, window_index=index)
    assert kept == [str(gen1), str(base)]  # the live file always stays

    # an incomplete window (reducer mid-file) is not authoritative:
    # the mtime heuristic decides, as it always did
    for entry in index.values():
        entry["complete"] = False
    kept = iter_trace_files(str(base), since_ts=since, window_index=index)
    assert kept == [str(gen2), str(base)]
    assert kept == iter_trace_files(str(base), since_ts=since)  # no index


# -- breaker board at scale ---------------------------------------------------


class _NoIterDict(dict):
    """A member map that fails the test on any full-map iteration —
    ``len()`` and keyed access stay legal."""

    def _refuse(self, *args, **kwargs):
        raise AssertionError("board summary iterated the full member map")

    __iter__ = _refuse
    keys = _refuse
    values = _refuse
    items = _refuse
    copy = _refuse


def test_breaker_summary_never_iterates_member_map(tmp_path):
    """5k tracked members, 8 tripped: the bounded summary costs
    O(unhealthy) — the full map is only ever ``len()``-counted."""
    board = fleetgen.make_breaker_board(5000, tripped=8)
    board._members = _NoIterDict(board._members)

    summary = board.summary(top_k=5)
    assert summary["tracked"] == 5000
    assert summary["open"] == 8
    assert summary["half_open"] == 0
    assert summary["trips"] == 8
    assert len(summary["members"]) == 5
    assert all(m["trips"] >= 1 for m in summary["members"])

    # the compatibility spelling rides the same bounded path
    legacy = board.snapshot(detail_cap=0)
    assert legacy["open"] == 8 and legacy["members"] == []


# -- the generator itself -----------------------------------------------------


def test_fleetgen_plan_covers_every_member():
    plan = fleetgen.build_fleet_plan(256)
    totals = plan.doc["totals"]
    assert totals["members"] == 256
    assert 1 <= totals["buckets"] < 256  # families coalesce, like a real fleet


def test_fleetgen_corpora_are_deterministic(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    names = fleetgen.machine_names(32)
    path_a, first, last = fleetgen.write_span_corpus(str(a), 500, names)
    path_b, _, _ = fleetgen.write_span_corpus(str(b), 500, names)
    assert Path(path_a).read_bytes() == Path(path_b).read_bytes()
    assert first == fleetgen.EPOCH and last > first
