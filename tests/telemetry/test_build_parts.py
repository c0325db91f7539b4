"""
Inside the build phases (PR 24): ``build_part`` spans and the seconds
they leave in ``build_status.json``, the compile path's counters, the
profiler annotations on the spans, and what all of it may cost in lines.
"""

import ast
import concurrent.futures
import glob
import json
import os
import time

import numpy as np
import pytest

from gordo_tpu import telemetry
from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder
from gordo_tpu.telemetry import BuildProgress, SpanRecorder, device, load_status
from gordo_tpu.telemetry.progress import BUILD_TRACE_FILE
from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis

from .test_trace_schema import assert_span_schema

pytestmark = pytest.mark.observability

MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "gordo_tpu.models.JaxAutoEncoder": {
                "kind": "feedforward_hourglass",
                "encoding_layers": 1,
                "epochs": 1,
            }
        }
    }
}


#: what two reads of two clocks may differ by where nothing ran between
#: ("to clock resolution"): microseconds on plain Linux, but a kernel
#: that accounts CPU by scheduler ticks steps ``time.thread_time()`` by
#: 10 ms (the chip's host does: PERF.md 6, PR 37), so two ticks
CLOCK_SLACK = 0.025

#: parts a dense job must record, by phase
REQUIRED_PARTS = {
    "data_fetch": {
        "machine_fetch", "provider_read", "resample_join", "row_filter", "pool_start",
    },
    "cv_train": {"stack", "h2d", "init", "collect"},
    "final_fit": {"stack", "h2d", "init", "collect"},
    "cv_predict": {"stack", "collect"},
    "cv_score": {"stack", "device_scores", "metric_scores", "thresholds"},
    "dump": {"serialize", "write"},
}

COMPILE_KEYS = {
    "trace_s", "lower_s", "backend_s", "cache_load_s",
    "programs", "persistent_hits", "persistent_misses",
}


def make_machine(name):
    return Machine.from_config(
        {
            "name": name,
            "model": MODEL,
            "dataset": {
                "type": "RandomDataset",
                "train_start_date": "2020-01-01T00:00:00+00:00",
                "train_end_date": "2020-01-05T00:00:00+00:00",
                "tag_list": ["t1", "t2"],
            },
        },
        project_name="parts-test",
    )


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """One two-machine build with its config load and reporters handed
    to the builder, as the ``build-fleet`` command does."""
    out = str(tmp_path_factory.mktemp("parts") / "out")
    started = time.perf_counter()
    builder = FleetBuilder([make_machine("bp-a"), make_machine("bp-b")])
    results = builder.build(output_dir=out, started=started, report=True)
    assert len(results) == 2
    with open(os.path.join(out, BUILD_TRACE_FILE)) as f:
        spans = [json.loads(line) for line in f]
    return builder, spans, load_status(out)


def by_id(spans):
    return {s["context"]["span_id"]: s for s in spans}


# -- the recorder ---------------------------------------------------------------


def test_record_places_an_interval_where_its_start_says():
    rec = SpanRecorder()
    with rec.span("build_phase", phase="data_fetch") as phase:
        rec.record("build_part", 2.5, start=1_700_000_000.0, part="machine_fetch")
        rec.record("queue_wait", 0.25)
    placed, now = rec.finished("build_part")[0], rec.finished("queue_wait")[0]
    assert placed["start_time"].startswith("2023-11-14T22:13:20")
    assert placed["end_time"].startswith("2023-11-14T22:13:22.5")
    assert placed["duration_ms"] == 2500.0
    assert placed["parent_id"] == now["parent_id"] == phase.span_id
    assert placed["attributes"] == {"part": "machine_fetch"}
    assert now["duration_ms"] == 250.0 and now["end_time"] > "2024"


def test_explicit_parent_wins_over_the_thread_stack():
    rec = SpanRecorder()
    with rec.span("outer") as outer:
        with rec.span("inner", parent_id="f" * 16):
            pass
        with rec.span("nested"):
            pass
    spans = {s["name"]: s for s in rec.finished()}
    assert spans["inner"]["parent_id"] == "f" * 16
    assert spans["nested"]["parent_id"] == outer.span_id
    assert "parent_id" not in spans["inner"]["attributes"]


def test_a_pool_thread_span_names_its_parent():
    """A pool thread has no enclosing span: without ``parent_id`` its
    span is an orphan, with it a child of the span it works for."""
    rec = SpanRecorder()
    with rec.span("build_phase", phase="data_fetch") as phase:

        def work(parent):
            with rec.span("build_part", parent_id=parent, part="machine_fetch"):
                pass

        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            list(pool.map(work, [phase.span_id, None]))
    parents = sorted(
        str(s["parent_id"]) for s in rec.finished("build_part")
    )
    assert parents == sorted([phase.span_id, "None"])


def test_duration_is_monotonic_and_the_start_stamp_wall_time(monkeypatch):
    """A wall clock stepped back inside a span cannot make its duration
    negative: the duration comes from ``time.perf_counter()``."""
    from gordo_tpu.telemetry import recorder as recorder_module

    stamps = iter([1_700_000_100.0, 1_700_000_000.0])
    real_time = time.time
    monkeypatch.setattr(
        recorder_module.time, "time", lambda: next(stamps, real_time())
    )
    rec = SpanRecorder()
    with rec.span("stepped"):
        time.sleep(0.01)
    (span,) = rec.finished()
    assert span["duration_ms"] >= 10.0
    assert span["start_time"].startswith("2023-11-14")
    assert span["end_time"] >= span["start_time"]


def test_the_annotate_hook_wraps_the_body_of_the_spans_it_takes():
    entered = []

    class Region:
        def __init__(self, label):
            self.label = label

        def __enter__(self):
            entered.append(("enter", self.label))

        def __exit__(self, *exc):
            entered.append(("exit", self.label))

    rec = SpanRecorder()
    rec.annotate = lambda name, attrs: (
        Region(f"{name}:{attrs.get('phase')}") if name == "build_phase" else None
    )
    with rec.span("build_phase", phase="plan"):
        entered.append(("body", None))
    with rec.span("other"):
        pass
    with pytest.raises(ValueError):
        with rec.span("build_phase", phase="dump"):
            raise ValueError("boom")
    assert entered == [
        ("enter", "build_phase:plan"), ("body", None), ("exit", "build_phase:plan"),
        ("enter", "build_phase:dump"), ("exit", "build_phase:dump"),
    ]
    assert rec.finished("build_phase")[1]["status"]["status_code"] == "ERROR"


def test_part_span_stamps_the_recorders_phase():
    rec = SpanRecorder()
    rec.phase = "cv_train"
    with telemetry.activate(rec):
        with telemetry.part_span("stack", rows=3):
            pass
    (span,) = rec.finished("build_part")
    cpu = span["attributes"].pop("cpu_seconds")  # the recorder's, on every part
    assert 0.0 <= cpu <= span["duration_ms"] / 1000.0 + CLOCK_SLACK
    assert span["attributes"] == {"phase": "cv_train", "part": "stack", "rows": 3}
    # without a build the same call records nothing and costs nothing
    with telemetry.part_span("stack"):
        pass
    assert telemetry.get_recorder() is telemetry.NULL_RECORDER
    assert telemetry.NULL_RECORDER.phase == ""



# -- the second clock -------------------------------------------------------------


def _spin(cpu_seconds):
    """Compute until this thread has used ``cpu_seconds`` of CPU."""
    began = time.thread_time()
    while time.thread_time() - began < cpu_seconds:
        pass


def test_a_part_that_spins_reads_cpu_near_its_wall_and_one_that_sleeps_near_none():
    rec = SpanRecorder()
    with rec.span("build_part", cpu_clock=True, part="spin"):
        _spin(0.1)
    with rec.span("build_part", cpu_clock=True, part="sleep"):
        time.sleep(0.1)
    spin, sleep = rec.finished("build_part")
    wall = spin["duration_ms"] / 1000.0
    # all of its seconds but what the host took this thread off the core for
    assert 0.1 <= spin["attributes"]["cpu_seconds"] <= wall + CLOCK_SLACK
    assert spin["attributes"]["cpu_seconds"] >= 0.2 * wall
    assert sleep["duration_ms"] >= 100.0
    assert sleep["attributes"]["cpu_seconds"] <= CLOCK_SLACK


def test_only_a_span_that_asks_reads_the_cpu_clock_and_the_build_paths_ask():
    rec = SpanRecorder()
    with rec.span("serve_batch"):
        pass
    with rec.span("serve_batch", cpu_clock=True):
        pass
    plain, timed = rec.finished("serve_batch")
    assert "cpu_seconds" not in plain["attributes"]
    assert timed["attributes"].keys() == {"cpu_seconds"}
    # the build path's four ways to open a span all ask
    builder = FleetBuilder([make_machine("cpu-b")])
    builder.recorder = rec
    with telemetry.activate(rec):
        with builder._phase("stage"):
            with builder._part("on-builder"):
                pass
            with telemetry.part_span("on-trainer"):
                pass
            with telemetry.program_span("fleet_fit", ("cpu-clock-test",)):
                pass
    asked = rec.finished("build_phase") + rec.finished("build_part") + rec.finished("device_program")
    assert len(asked) == 4 and all("cpu_seconds" in s["attributes"] for s in asked)
    assert "process_cpu_seconds" in rec.finished("build_phase")[0]["attributes"]
    # work timed in place hands its own reading over, rounded, never negative
    rec.record("build_part", 1.0, cpu_seconds=0.25000049, part="write", count=3)
    rec.record("build_part", 1.0, cpu_seconds=-1e-9, part="serialize")
    rec.record("build_part", 1.0, part="plain")
    write, serialize, plain = rec.finished("build_part")[2:]
    assert write["attributes"] == {"part": "write", "count": 3, "cpu_seconds": 0.25}
    assert serialize["attributes"]["cpu_seconds"] == 0.0
    assert "cpu_seconds" not in plain["attributes"]


def test_a_build_that_records_nothing_reads_no_second_clock_and_walks_no_tree(monkeypatch):
    """Under the null recorder a span's handle says it records nothing,
    so the sites that would compute something only to ``set`` it (a
    tree's bytes, the fetch's seconds) skip it, and work timed in place
    is timed on a clock that stands still."""
    import gordo_tpu.parallel.fleet as fleet

    with telemetry.NULL_RECORDER.span("build_part", cpu_clock=True) as handle:
        assert handle.recording is False
        handle.set(bytes=1)  # taken, kept for nobody
    with SpanRecorder().span("build_part") as handle:
        assert handle.recording is True
    builder = FleetBuilder([make_machine("cpu-c")])
    assert builder.recorder is telemetry.NULL_RECORDER
    clock = builder._cpu_clock()
    assert clock() == clock() == 0.0
    builder.recorder = SpanRecorder()
    assert builder._cpu_clock() is time.thread_time

    def no_walk(*trees, **kwargs):
        raise AssertionError("a tree was walked for a span nobody records")

    monkeypatch.setattr(fleet, "tree_nbytes", no_walk)
    tree = {"w": np.ones((2, 3), np.float32)}
    with telemetry.part_span("collect") as span:
        host = fleet._fetch_for(span, tree)
    np.testing.assert_array_equal(host["w"], tree["w"])


def test_a_pool_of_four_spinning_parts_sums_four_threads_cpu(tmp_path):
    """Under the GIL the four take turns, so each part's wall seconds are
    about the four's CPU together; the CPU clock is each thread's own."""
    builder = FleetBuilder([make_machine("cpu-a")])
    builder.recorder = rec = SpanRecorder()
    rec.add_listener(builder._export_span)
    builder.progress = BuildProgress(str(tmp_path), project="p", total=1)

    def work(_):
        with builder._part("spin"):
            _spin(0.1)

    with builder._phase("dump"):
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            list(pool.map(work, range(4)))
    spans = [s for s in rec.finished("build_part")]
    assert len(spans) == 4
    for span in spans:
        assert 0.1 <= span["attributes"]["cpu_seconds"] <= span["duration_ms"] / 1000.0 + CLOCK_SLACK
    builder.progress.write(force=True)
    phase = load_status(str(tmp_path))["phases"]["dump"]
    folded = phase["parts"]["spin"]
    assert folded["count"] == 4 and 0.4 <= folded["cpu_seconds"] <= folded["seconds"] + 4 * CLOCK_SLACK
    # the phase's own thread only waited; the process computed the four's sum
    assert phase["cpu_seconds"] < 0.1
    assert phase["process_cpu_seconds"] >= 0.4


# -- the builder ----------------------------------------------------------------


def test_builder_part_from_a_pool_thread_hangs_under_the_running_phase():
    builder = FleetBuilder([make_machine("pp-a")])
    builder.recorder = rec = SpanRecorder()
    with builder._phase("dump"):
        with builder._part("on-main"):
            pass
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            pool.submit(_part_in_thread, builder).result(timeout=30)
    (phase,) = rec.finished("build_phase")
    parts = {s["attributes"]["part"]: s for s in rec.finished("build_part")}
    assert parts["on-main"]["parent_id"] == parts["whole"]["parent_id"] == phase["context"]["span_id"]
    cpu = parts["whole"]["attributes"].pop("cpu_seconds")  # every part's, from its own thread
    assert 0.0 <= cpu <= parts["whole"]["duration_ms"] / 1000.0 + CLOCK_SLACK
    assert parts["whole"]["attributes"] == {"phase": "dump", "part": "whole"}
    assert rec.phase == "" and builder._phase_span_id is None  # restored at the phase's end


def _part_in_thread(builder):
    with builder._part("whole"):
        pass


def test_parts_nest_under_their_phase_from_the_main_and_from_pool_threads(built):
    _, spans, _ = built
    index = by_id(spans)
    parts = [s for s in spans if s["name"] == "build_part"]
    assert parts
    for span in parts:
        assert_span_schema(span)
        # every part hangs in the tree under the phase it names: directly,
        # or inside another part or a device program of that phase
        node = span
        while node["name"] != "build_phase":
            assert node["parent_id"] in index, (span["attributes"], node["name"])
            node = index[node["parent_id"]]
        assert node["attributes"]["phase"] == span["attributes"]["phase"]
    fetches = [s for s in parts if s["attributes"]["part"] == "machine_fetch"]
    assert len(fetches) == 2  # one a machine: timed on the pool's threads, written by the main
    for span in fetches:
        assert index[span["parent_id"]]["attributes"]["phase"] == "data_fetch"
    # the trainer's parts (the global recorder) hang under the phase too,
    # the fetch of predictions inside its program
    inits = [s for s in parts if s["attributes"]["part"] == "init"]
    assert {index[s["parent_id"]]["attributes"]["phase"] for s in inits} == {
        "cv_train", "final_fit",
    }
    (collect,) = [
        s for s in parts
        if s["attributes"]["part"] == "collect"
        and s["attributes"]["phase"] == "cv_predict"
    ]
    assert index[collect["parent_id"]]["name"] == "device_program"


def test_machine_fetch_carries_machine_rows_retries_and_the_datasets_parts(built):
    """One line a machine: the dataset's own parts ride on it as
    ``<part>_s`` attributes, not as spans of their own."""
    _, spans, _ = built
    fetches = [
        s for s in spans
        if s["name"] == "build_part" and s["attributes"]["part"] == "machine_fetch"
    ]
    assert sorted(s["attributes"]["machine"] for s in fetches) == ["bp-a", "bp-b"]
    for span in fetches:
        attributes = span["attributes"]
        assert set(attributes) == {
            "phase", "part", "machine", "rows", "retries", "cpu_seconds", "worker",
            "provider_read_s", "resample_join_s", "row_filter_s",
            "provider_read_cpu_seconds", "resample_join_cpu_seconds",
            "row_filter_cpu_seconds",
        }
        assert attributes["rows"] > 0 and attributes["retries"] == 0
        # two machines are under the line: fetched on the builder's threads
        assert attributes["worker"] == "thread"
        nested = telemetry.nested_part_seconds(attributes)
        assert set(nested) == {"provider_read", "resample_join", "row_filter"}
        assert 0 < sum(nested.values()) <= span["duration_ms"] / 1000.0
        # the pool thread read its CPU clock where it read the wall clock:
        # the folding rule takes no CPU attribute for a part of its own
        nested_cpu = telemetry.nested_part_cpu_seconds(attributes)
        assert set(nested_cpu) == set(nested)
        for part, seconds in nested_cpu.items():
            assert 0.0 <= seconds <= nested[part] + CLOCK_SLACK
        assert sum(nested_cpu.values()) <= attributes["cpu_seconds"] + CLOCK_SLACK
        assert attributes["cpu_seconds"] <= span["duration_ms"] / 1000.0 + CLOCK_SLACK
    assert not [
        s for s in spans
        if s["name"] == "build_part" and s["attributes"]["part"] == "provider_read"
    ]


@pytest.mark.parametrize("phase", sorted(REQUIRED_PARTS))
def test_status_holds_the_parts_of_each_phase(built, phase):
    builder, _, status = built
    entry = status["phases"][phase]
    assert set(entry["parts"]) >= REQUIRED_PARTS[phase]
    workers = builder.data_workers if phase == "data_fetch" else 8
    for part, measured in entry["parts"].items():
        # the parts of the host's scoring count the machine-folds that
        # fell back to it: none of a default evaluation's
        fell_back = phase == "cv_score" and part in ("metric_scores", "thresholds")
        # and the pool's start counts the workers started: none for a job
        # of two machines in a process that has no pool (dataset/fetch_pool.py)
        none_started = part == "pool_start"
        assert measured["count"] >= (0 if fell_back or none_started else 1)
        if none_started:
            assert measured["count"] == 0 and measured["cpu_seconds"] == 0.0
        assert measured["seconds"] >= 0.0
        # thread-seconds: no more than the phase's wall seconds on
        # every worker at once
        assert measured["seconds"] <= entry["seconds"] * workers + 1e-3, part


def test_cv_score_counts_the_machine_folds_scored_on_the_device(built):
    """A default evaluation is scored by the predict program: the device
    part counts machines x folds, the host's parts none; the program's
    name holds no ``fit`` (the chip benchmark holds every program so
    named to the cell's epochs and samples)."""
    _, spans, status = built
    parts = status["phases"]["cv_score"]["parts"]
    assert parts["device_scores"]["count"] == 2 * 3
    assert parts["metric_scores"]["count"] == parts["thresholds"]["count"] == 0
    programs = [
        s["attributes"]["program"] for s in spans if s["name"] == "device_program"
    ]
    assert programs == ["fleet_fit", "fleet_predict_score", "fleet_fit"]
    assert "fit" not in programs[1]


def test_cv_score_counts_the_machine_folds_that_fell_back(tmp_path):
    """A metric the program cannot express sends that machine's folds to
    the host's code, its neighbour's stay on the device; a second build
    in the same process compiles nothing new for scoring."""
    from gordo_tpu.parallel.fleet import _fleet_predict_score_program

    def machines():
        odd = make_machine("bp-odd")
        odd.evaluation = {**odd.evaluation, "metrics": ["median_absolute_error"]}
        return [make_machine("bp-plain"), odd]

    compiled = []
    for attempt in ("first", "second"):
        out = str(tmp_path / attempt)
        results = FleetBuilder(machines()).build(output_dir=out)
        assert len(results) == 2
        parts = load_status(out)["phases"]["cv_score"]["parts"]
        assert parts["device_scores"]["count"] == 3
        assert parts["metric_scores"]["count"] == parts["thresholds"]["count"] == 3
        with open(os.path.join(out, BUILD_TRACE_FILE)) as f:
            spans = [json.loads(line) for line in f]
        (predict,) = [
            s for s in spans
            if s["name"] == "device_program"
            and s["attributes"]["program"] == "fleet_predict_score"
        ]
        program = _fleet_predict_score_program(results[0][0].base_estimator.spec_, None)
        compiled.append((predict["attributes"]["compile"], program, program._cache_size()))
    (_, first, size), (compile_flag, second, size_after) = compiled
    # (>= 1: another test file's builds in this process may have compiled
    # the program for their shapes before)
    assert compile_flag is False and second is first and size_after == size >= 1


def test_no_part_written_by_one_thread_computed_longer_than_it_took(built):
    """``cpu_seconds <= seconds`` on every ``build_part`` span but the
    sums over a pool's threads (``count``), and on every phase's own
    thread; the fetch alone is no longer than its ``collect``."""
    _, spans, status = built
    parts = [s for s in spans if s["name"] == "build_part"]
    assert all("cpu_seconds" in s["attributes"] for s in parts)
    for span in parts:
        if "count" not in span["attributes"]:
            assert (
                0.0 <= span["attributes"]["cpu_seconds"]
                <= span["duration_ms"] / 1000.0 + CLOCK_SLACK
            ), span["attributes"]
    for span in spans:
        if span["name"] == "build_phase" and "cpu_seconds" in span["attributes"]:
            assert span["attributes"]["cpu_seconds"] <= span["duration_ms"] / 1000.0 + CLOCK_SLACK
            assert span["attributes"]["process_cpu_seconds"] >= 0.0
    collects = [s for s in parts if s["attributes"]["part"] == "collect"]
    assert len(collects) == 3  # cv fit, cv predict, final fit
    for span in collects:
        assert 0.0 < span["attributes"]["d2h_seconds"] <= span["duration_ms"] / 1000.0
        assert span["attributes"]["bytes"] > 0


PARTS_WITH_BYTES = [
    ("cv_train", "stack"), ("cv_train", "h2d"), ("cv_train", "collect"),
    ("final_fit", "stack"), ("final_fit", "h2d"), ("final_fit", "collect"),
    ("cv_predict", "stack"), ("cv_predict", "h2d"), ("cv_predict", "collect"),
    ("cv_score", "stack"), ("dump", "write"),
]


@pytest.mark.parametrize("phase,part", PARTS_WITH_BYTES)
def test_status_keeps_the_bytes_and_the_cpu_of_a_part_that_moved_data(built, phase, part):
    _, _, status = built
    measured = status["phases"][phase]["parts"][part]
    assert isinstance(measured["bytes"], int) and measured["bytes"] > 0
    assert measured["cpu_seconds"] >= 0.0
    if part == "collect":
        # the fetch alone rides on the entry: the entry's seconds are the
        # whole part still, and no part of the phase is the fetch again
        assert 0.0 < measured["d2h_seconds"] <= measured["seconds"]
        assert not {"d2h", "d2h_seconds"} & set(status["phases"][phase]["parts"])
    else:
        assert "d2h_seconds" not in measured


def test_status_has_a_key_only_where_a_span_gave_it(built):
    _, _, status = built
    phases = status["phases"]
    for phase, part in (("cv_train", "init"), ("dump", "serialize"), ("data_fetch", "pool_start")):
        assert set(phases[phase]["parts"][part]) == {"seconds", "count", "cpu_seconds"}
    # a fetch says where it was computed: both machines on the builder's threads
    fetched = phases["data_fetch"]["parts"]["machine_fetch"]
    assert set(fetched) == {"seconds", "count", "cpu_seconds", "in_process"}
    assert (fetched["count"], fetched["in_process"]) == (2, 0)
    # the dataset's parts have their pool threads' CPU, by the paired attribute
    fetch = phases["data_fetch"]["parts"]
    nested = ("provider_read", "resample_join", "row_filter")
    for part in nested:
        assert set(fetch[part]) == {"seconds", "count", "cpu_seconds"}
        assert fetch[part]["cpu_seconds"] <= fetch[part]["seconds"] + 2 * CLOCK_SLACK
    assert sum(fetch[p]["cpu_seconds"] for p in nested) <= (
        fetch["machine_fetch"]["cpu_seconds"] + 2 * CLOCK_SLACK
    )
    # the blocks a dense stack fills: X of two tags and two weight planes
    # over the members' padded rows, float32 (no second array of targets)
    (fit,) = [
        s["attributes"] for s in built[1]
        if s["name"] == "device_program" and s["attributes"].get("members") == 2
    ]
    members, rows, tags = ast.literal_eval(fit["shape"])
    assert phases["final_fit"]["parts"]["stack"]["bytes"] == members * rows * (tags + 2) * 4


def test_the_newest_resource_sample_reaches_the_status_and_no_event_is_written(built):
    _, spans, status = built
    resources = status["resources"]
    assert set(resources) == {"hbm_peak_bytes", "host_rss_peak_bytes", "host_cpu_count"}
    assert resources["host_rss_peak_bytes"] > 50e6  # a process that imported JAX
    assert resources["host_cpu_count"] == len(os.sched_getaffinity(0))
    # the CPU backend reports no memory: not measured, not zero
    assert resources["hbm_peak_bytes"] is None
    assert not [s for s in spans if s["name"] == "device_utilization"]
    assert "Resources: host_rss_peak_bytes=" in telemetry.render_status(status)


def test_a_phase_without_parts_is_written_as_before(built):
    _, _, status = built
    for phase in ("plan", "stage", "assemble", "cv_finalize"):
        assert set(status["phases"][phase]) == {
            "seconds", "status", "cpu_seconds", "process_cpu_seconds",
        }
    # timed by the command before the build had a recorder: wall seconds alone
    assert set(status["phases"]["config_load"]) == {"seconds", "status"}


def test_config_load_and_report_are_phases_and_complete_is_the_last_write(built):
    _, spans, status = built
    assert status["state"] == "complete" and status["phase"] is None
    order = list(status["phases"])
    assert order[0] == "config_load" and order[-1] == "report"
    assert status["phases"]["config_load"]["seconds"] > 0
    phases = [s for s in spans if s["name"] == "build_phase"]
    assert phases[0]["attributes"]["phase"] == "config_load"
    assert phases[-1]["attributes"]["phase"] == "report"
    for span in phases:
        assert span["end_time"] >= span["start_time"]


def test_almost_nothing_of_a_build_lies_between_its_phases(built):
    _, spans, status = built
    root = next(s for s in spans if s["name"] == "fleet_build")
    inside = sum(
        entry["seconds"] for name, entry in status["phases"].items()
        if name != "config_load"
    )
    assert inside <= root["duration_ms"] / 1000.0 + 1e-3
    assert inside >= 0.97 * root["duration_ms"] / 1000.0


def test_compile_counters_in_the_status_and_on_the_root_span(built):
    _, spans, status = built
    assert set(status["compile"]) == COMPILE_KEYS
    assert all(value >= 0 for value in status["compile"].values())
    # the build traced, lowered and built its programs in this process
    assert status["compile"]["programs"] >= 1
    assert status["compile"]["trace_s"] > 0 and status["compile"]["lower_s"] > 0
    root = next(s for s in spans if s["name"] == "fleet_build")
    assert {k: root["attributes"][k] for k in COMPILE_KEYS} == status["compile"]


def test_dense_fit_spans_and_the_status_carry_shuffle_columns(built):
    """Both fit programs of a dense autoencoder's build shuffled rows of
    its 2 tags and the weight, and no second array for the targets; the
    status keeps it a fit program, as it keeps a backbone's counters."""
    _, spans, status = built
    fits = [
        s for s in spans
        if s["name"] == "device_program" and s["attributes"]["program"] == "fleet_fit"
    ]
    assert len(fits) == 2
    for span in fits:
        assert_span_schema(span)
        attributes = span["attributes"]
        assert attributes["shuffle_columns"] == 3 and attributes["validation_slots"] == 0
        assert attributes["fit_counters"] == ["shuffle_columns"]
    assert [
        (c["program"], c["members"], c["shuffle_columns"]) for c in status["fit_counters"]
    ] == [("fleet_fit", 6, 3), ("fleet_fit", 2, 3)]  # three folds' members, then the fits


def test_span_budget_of_a_two_machine_build(built):
    """At most 5 added spans a machine and 8 a device program over the
    lines the parent wrote (which for this job were 45: its phases,
    programs and per-machine events)."""
    _, spans, _ = built
    machines = 2
    programs = sum(1 for s in spans if s["name"] == "device_program")
    assert programs == 3
    parts = [s for s in spans if s["name"] == "build_part"]
    per_machine = [s for s in parts if s["attributes"]["part"] == "machine_fetch"]
    assert len(per_machine) == machines
    assert len(parts) - len(per_machine) <= 8 * programs
    phases = {s["attributes"]["phase"] for s in spans if s["name"] == "build_phase"}
    assert len(spans) <= 45 + 5 * machines + 8 * programs + len(
        phases & {"config_load", "report", "prepare", "cv_split", "finish"}
    )


def test_telemetry_off_records_no_parts_and_builds_the_same(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.TELEMETRY_ENV, "0")
    out = str(tmp_path / "out")
    builder = FleetBuilder([make_machine("off-a")])
    results = builder.build(output_dir=out, started=time.perf_counter(), report=True)
    assert len(results) == 1
    assert not os.path.exists(os.path.join(out, BUILD_TRACE_FILE))
    assert load_status(out) is None
    assert telemetry.NULL_RECORDER.phase == ""  # the shared null recorder stays clean
    assert builder.phase_seconds["config_load"] > 0 and "report" in builder.phase_seconds


def test_a_failing_reporter_fails_the_build_like_any_phase(tmp_path, monkeypatch):
    out = str(tmp_path / "out")
    monkeypatch.setattr(
        Machine, "report", lambda self: (_ for _ in ()).throw(RuntimeError("no sink"))
    )
    with pytest.raises(RuntimeError, match="no sink"):
        FleetBuilder([make_machine("rep-a")]).build(output_dir=out, report=True)
    assert load_status(out)["state"] == "failed"


# -- the profiler's clock ---------------------------------------------------------


def test_phases_parts_and_programs_are_annotations_in_a_profiler_session(
    tmp_path, monkeypatch
):
    """With a session that is not ``GORDO_TPU_PROFILE_DIR``'s (the chip
    benchmark's, here the test's own) the build's phases, parts and
    device programs lie in the trace's host plane."""
    import jax
    from jax.profiler import ProfileData

    monkeypatch.delenv("GORDO_TPU_PROFILE_DIR", raising=False)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1  # what benchmarks/chip/procs/common.Trace asks for
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        FleetBuilder([make_machine("an-a")]).build(output_dir=str(tmp_path / "out"))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(event.name for event in line.events)
    assert "build_phase:data_fetch" in names and "build_phase:cv_train" in names
    assert "build_part:data_fetch/machine_fetch" in names
    assert "dataset:provider_read" in names  # the dataset's own, by the same door
    assert "build_part:cv_train/init" in names  # the trainer's
    assert "device_program:fleet_fit" in names
    assert "device_program:fleet_predict_score" in names


# -- the compile path's counters -------------------------------------------------


def test_compile_path_counters_sum_durations_and_keep_backend_net_of_cache_reads():
    device.watch_compile_path()
    device.watch_compile_path()  # idempotent
    before = device.compile_path_counters()
    assert set(before) == COMPILE_KEYS
    device._on_jax_duration(device._TRACE_EVENT, 0.25, fun_name="f")
    device._on_jax_duration(device._LOWER_EVENT, 0.5)
    # the backend call that encloses a cache read of 0.75 s
    device._on_jax_duration(device._BACKEND_EVENT, 1.0)
    device._on_jax_duration(device._CACHE_LOAD_EVENT, 0.75)
    device._on_jax_duration("/jax/some/other_duration", 9.0)
    device._on_jax_event("/jax/compilation_cache/cache_hits")
    after = device.compile_path_counters()
    moved = {key: round(after[key] - before[key], 6) for key in COMPILE_KEYS}
    assert moved == {
        "trace_s": 0.25, "lower_s": 0.5, "backend_s": 0.25, "cache_load_s": 0.75,
        "programs": 1, "persistent_hits": 1, "persistent_misses": 0,
    }


def test_a_jit_made_anew_shows_in_the_counters():
    """What ``job_retrace_s`` reads: a fresh ``jax.jit`` of a known
    function traces and lowers again even though nothing is new."""
    import jax
    import jax.numpy as jnp

    device.watch_compile_path()
    x = jnp.ones(3)
    readings = []
    for _ in range(2):
        before = device.compile_path_counters()
        jax.jit(lambda x: x * 2 + 1)(x).block_until_ready()
        after = device.compile_path_counters()
        readings.append(after["programs"] - before["programs"])
    assert readings == [1, 1]  # the second jit is new to JAX, not to XLA
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]


# -- the status document ----------------------------------------------------------


def test_progress_parts_and_compile_only_where_recorded(tmp_path):
    seconds = {"data_fetch": 2.0, "plan": 0.1}
    progress = BuildProgress(str(tmp_path), project="p", total=2, phase_seconds=seconds)
    progress.phase("plan")
    progress.phase("data_fetch")
    progress.add_part("data_fetch", "machine_fetch", 1.5)
    progress.add_part("data_fetch", "machine_fetch", 2.5)
    progress.add_part("data_fetch", "write", 4.0, count=160)
    progress.add_part("never_entered", "x", 1.0)
    progress.write(force=True)
    doc = load_status(str(tmp_path))
    assert "compile" not in doc
    assert doc["phases"]["plan"] == {"seconds": 0.1, "status": "done"}
    assert doc["phases"]["data_fetch"]["parts"] == {
        "machine_fetch": {"seconds": 4.0, "count": 2},
        "write": {"seconds": 4.0, "count": 160},
    }
    assert "never_entered" not in doc["phases"]
    progress.compile = {"trace_s": 0.5, "programs": 2}
    progress.finish("complete")
    doc = load_status(str(tmp_path))
    assert doc["compile"] == {"trace_s": 0.5, "programs": 2}
    rendered = telemetry.render_status(doc)
    assert "machine_fetch" in rendered and "x2 (thread-seconds)" in rendered
    assert "Compile path: trace_s=0.5, programs=2" in rendered


def test_progress_sums_cpu_bytes_and_d2h_where_given_and_renders_the_rates(tmp_path):
    seconds = {"cv_train": 8.0}
    progress = BuildProgress(str(tmp_path), project="p", total=1, phase_seconds=seconds)
    progress.phase("cv_train")
    progress.add_part("cv_train", "collect", 1.5, cpu_seconds=0.5, bytes=10**9, d2h_seconds=0.5)
    progress.add_part("cv_train", "collect", 0.5, cpu_seconds=0.25, bytes=10**9, d2h_seconds=0.5)
    progress.add_part("cv_train", "init", 0.25, cpu_seconds=None, bytes=None)
    progress.add_part("cv_train", "stack", 0.5, bytes=2 * 10**9, unknown=7)
    progress.add_phase_cpu("cv_train", cpu_seconds=1.0, process_cpu_seconds=12.0)
    progress.add_phase_cpu("cv_train", cpu_seconds=0.5, process_cpu_seconds=None)
    progress.add_phase_cpu("never_entered", cpu_seconds=1.0)
    progress.resources = {
        "hbm_peak_bytes": 5 * 10**9, "host_rss_peak_bytes": 2 * 10**9, "host_cpu_count": 13,
    }
    progress.write(force=True)
    doc = load_status(str(tmp_path))
    assert doc["phases"]["cv_train"] == {
        "seconds": 8.0, "status": "running",
        "cpu_seconds": 1.5, "process_cpu_seconds": 12.0,
        "parts": {
            "collect": {
                "seconds": 2.0, "count": 2, "cpu_seconds": 0.75,
                "bytes": 2 * 10**9, "d2h_seconds": 1.0,
            },
            "init": {"seconds": 0.25, "count": 1},
            "stack": {"seconds": 0.5, "count": 1, "bytes": 2 * 10**9},
        },
    }
    assert doc["resources"]["host_cpu_count"] == 13
    rendered = telemetry.render_status(doc)
    assert "[1.50 cores busy, own thread cpu 19%]" in rendered  # 12.0 and 1.5 of 8.0 s
    assert "[cpu 38%, 1.00 GB/s, d2h 1.00 s at 2.00 GB/s]" in rendered  # collect
    assert "x1 (thread-seconds)  [4.00 GB/s]" in rendered  # stack: bytes, no CPU
    assert "Resources: hbm_peak_bytes=5.00 GB, host_rss_peak_bytes=2.00 GB, host_cpu_count=13" in rendered


@pytest.mark.parametrize(
    "entry,shown",
    [
        # a pool at work: many cores, the phase's own thread waiting on it
        ({"seconds": 8.0, "process_cpu_seconds": 20.0, "cpu_seconds": 0.4},
         "  [2.50 cores busy, own thread cpu 5%]"),
        # the builder's one thread computing
        ({"seconds": 2.0, "process_cpu_seconds": 2.0, "cpu_seconds": 1.9},
         "  [1.00 cores busy, own thread cpu 95%]"),
        # the host waiting for the device
        ({"seconds": 10.0, "process_cpu_seconds": 0.5, "cpu_seconds": 0.25},
         "  [0.05 cores busy, own thread cpu 2%]"),
        ({"seconds": 4.0, "process_cpu_seconds": 6.0}, "  [1.50 cores busy]"),
        ({"seconds": 4.0, "cpu_seconds": 1.0}, "  [own thread cpu 25%]"),
        # the fetch workers at work (their CPU is in the process's), a job
        # that fetched a machine on a thread beside them, a job of one
        ({"seconds": 2.0, "process_cpu_seconds": 18.0, "cpu_seconds": 0.16,
          "parts": {"machine_fetch": {"seconds": 16.0, "count": 160, "in_process": 160}}},
         "  [9.00 cores busy, own thread cpu 8%, 160 of 160 machines fetched in processes]"),
        ({"seconds": 2.0, "process_cpu_seconds": 3.0,
          "parts": {"machine_fetch": {"seconds": 3.0, "count": 17, "in_process": 16}}},
         "  [1.50 cores busy, 16 of 17 machines fetched in processes]"),
        ({"seconds": 0.5, "cpu_seconds": 0.25,
          "parts": {"machine_fetch": {"seconds": 0.4, "count": 1, "in_process": 0}}},
         "  [own thread cpu 50%, 0 of 1 machines fetched in processes]"),
        # an older program's fetch says nothing of its workers
        ({"seconds": 2.0, "process_cpu_seconds": 3.0,
          "parts": {"machine_fetch": {"seconds": 30.0, "count": 17}}}, "  [1.50 cores busy]"),
        # an older program's phase, and one too short to have seconds
        ({"seconds": 4.0, "status": "done"}, ""),
        ({"seconds": 0.0, "process_cpu_seconds": 1.0, "cpu_seconds": 1.0}, ""),
    ],
)
def test_a_phases_line_says_the_cores_busy_and_what_its_own_thread_computed(entry, shown):
    assert telemetry.progress.cores_busy_text(entry) == shown


@pytest.mark.parametrize(
    "measured,shown",
    [
        ({"seconds": 2.0, "count": 1, "cpu_seconds": 0.5}, "  [cpu 25%]"),
        ({"seconds": 2.0, "count": 1, "bytes": 10**9}, "  [0.50 GB/s]"),
        # a fetch that is its part whole, and one that is a fifth of it
        ({"seconds": 2.0, "count": 3, "cpu_seconds": 1.0, "bytes": 4 * 10**9, "d2h_seconds": 2.0},
         "  [cpu 50%, 2.00 GB/s, d2h 2.00 s at 2.00 GB/s]"),
        ({"seconds": 2.0, "count": 3, "bytes": 4 * 10**9, "d2h_seconds": 0.4},
         "  [2.00 GB/s, d2h 0.40 s at 10.00 GB/s]"),
        # a stack of 1.32 GB, all of it into buffers the pool held, and a
        # process's first, none of it
        ({"seconds": 0.15, "count": 1, "cpu_seconds": 0.15, "bytes": 1_320_000_000,
          "bytes_reused": 1_320_000_000},
         "  [cpu 100%, 8.80 GB/s, 1.32 GB, 100% reused]"),
        ({"seconds": 1.32, "count": 1, "bytes": 1_320_000_000, "bytes_reused": 0},
         "  [1.00 GB/s, 1.32 GB, 0% reused]"),
        # bytes on a part too short to have seconds: the size alone
        ({"seconds": 0.0, "count": 1, "cpu_seconds": 0.0, "bytes": 5 * 10**8}, "  [0.50 GB]"),
        # machines fetched in processes: what crossed back, which is no rate
        # of the worker-seconds the fetches took
        ({"seconds": 16.0, "count": 160, "cpu_seconds": 15.6, "bytes": 349_000_000,
          "in_process": 160}, "  [cpu 98%, 0.35 GB]"),
        ({"seconds": 2.0, "count": 1}, ""),
    ],
)
def test_a_parts_line_says_its_cpu_share_its_rate_and_its_fetch(measured, shown):
    assert telemetry.progress.part_rates_text(measured) == shown


def test_concurrent_parts_lose_no_update(tmp_path):
    import sys

    progress = BuildProgress(None, project="p", total=1)
    progress.phase("data_fetch")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(16) as pool:
            list(
                pool.map(
                    lambda i: progress.add_part("data_fetch", "machine_fetch", 0.5),
                    range(2000),
                    timeout=60,
                )
            )
    finally:
        sys.setswitchinterval(interval)
    measured = progress.document()["phases"]["data_fetch"]["parts"]["machine_fetch"]
    assert measured == {"seconds": 1000.0, "count": 2000}


# -- gordo-tpu trace ----------------------------------------------------------------


def synthetic_build_spans():
    rec = SpanRecorder()
    t0 = 1_700_000_000.0

    def span(name, span_id, parent, start, seconds, **attributes):
        return rec._span_dict(
            name, span_id, parent, t0 + start, t0 + start + seconds, attributes, None
        )

    return [
        span("fleet_build", "r" * 16, None, 0.0, 10.0, trace_s=0.25, programs=2),
        span("build_phase", "a" * 16, "r" * 16, 0.0, 4.0, phase="data_fetch"),
        # two pool threads overlap for 1 s: they cover 3 s of the phase's 4
        span("build_part", "b" * 16, "a" * 16, 0.5, 2.0, phase="data_fetch", part="machine_fetch",
             provider_read_s=0.75, rows=5),
        span("build_part", "c" * 16, "a" * 16, 1.5, 2.0, phase="data_fetch", part="machine_fetch",
             provider_read_s=0.25),
        # nested in another span: in the table, covers nothing twice
        span("build_part", "d" * 16, "b" * 16, 0.5, 1.0, phase="data_fetch", part="collect"),
        span("build_phase", "e" * 16, "r" * 16, 4.0, 3.0, phase="cv_score"),
        # recorded as sums: they cover their seconds
        span("build_part", "f" * 16, "e" * 16, 5.0, 2.0, phase="cv_score", part="metric_scores", count=6),
        span("build_part", "0" * 16, "e" * 16, 6.5, 0.5, phase="cv_score", part="thresholds", count=6),
        span("build_phase", "1" * 16, "r" * 16, 7.0, 2.0, phase="cv_train"),
        span("device_program", "2" * 16, "1" * 16, 7.5, 1.25, program="fleet_fit"),
        span("build_phase", "3" * 16, "r" * 16, 9.0, 0.5, phase="cv_train"),
    ]


def test_build_breakdown_self_time_is_what_no_part_covers():
    found = build_breakdown(synthetic_build_spans())
    fetch = found["phases"]["data_fetch"]
    assert fetch["seconds"] == 4.0 and fetch["self_seconds"] == 1.0
    assert fetch["parts"] == {
        "machine_fetch": {"seconds": 4.0, "count": 2},
        "provider_read": {"seconds": 1.0, "count": 2},
        "collect": {"seconds": 1.0, "count": 1},
    }
    score = found["phases"]["cv_score"]
    assert score["self_seconds"] == 0.5
    assert score["parts"]["metric_scores"] == {"seconds": 2.0, "count": 6}
    train = found["phases"]["cv_train"]
    assert train["entries"] == 2 and train["seconds"] == 2.5
    assert train["self_seconds"] == 1.25  # the program covers like a part
    assert train["parts"] == {"program fleet_fit": {"seconds": 1.25, "count": 1}}
    assert found["compile"] == {"trace_s": 0.25, "programs": 2}
    assert build_breakdown([]) is None


def test_build_breakdown_keeps_cpu_and_bytes_and_counts_the_fetch_once():
    """A ``collect`` that says how long its fetch alone took covers its
    phase once: ``d2h_seconds`` is a key of its entry, no part."""
    spans = synthetic_build_spans()
    rec = SpanRecorder()
    t0 = 1_700_000_000.0
    spans.append(
        rec._span_dict(
            "build_part", "4" * 16, "1" * 16, t0 + 8.75, t0 + 9.0,
            {"phase": "cv_train", "part": "collect", "cpu_seconds": 0.125,
             "bytes": 10**9, "d2h_seconds": 0.2},
            None,
        )
    )
    for span in spans:
        if span["attributes"].get("part") == "machine_fetch":
            span["attributes"].update(cpu_seconds=0.5, provider_read_cpu_seconds=0.125)
        if span["name"] == "build_phase" and span["attributes"]["phase"] == "cv_train":
            span["attributes"].update(cpu_seconds=0.25, process_cpu_seconds=2.5)
        if span["name"] == "device_program":
            span["attributes"].update(cpu_seconds=0.5, bytes=123)
    found = build_breakdown(spans)
    train = found["phases"]["cv_train"]
    assert train["self_seconds"] == 1.0  # 1.25 before the collect's 0.25 s
    assert train["cpu_seconds"] == 0.5 and train["process_cpu_seconds"] == 5.0  # two entries
    assert train["parts"] == {
        "program fleet_fit": {"seconds": 1.25, "count": 1, "cpu_seconds": 0.5},
        "collect": {
            "seconds": 0.25, "count": 1, "cpu_seconds": 0.125,
            "bytes": 10**9, "d2h_seconds": 0.2,
        },
    }
    fetch = found["phases"]["data_fetch"]
    assert fetch["self_seconds"] == 1.0 and "cpu_seconds" not in fetch
    assert fetch["parts"]["machine_fetch"] == {"seconds": 4.0, "count": 2, "cpu_seconds": 1.0}
    assert fetch["parts"]["provider_read"] == {"seconds": 1.0, "count": 2, "cpu_seconds": 0.25}
    rendered = render_analysis(
        {"trace": "t", "spans_read": len(spans), "build_breakdown": found}
    )
    assert "[2.00 cores busy, own thread cpu 20%]" in rendered  # 5.0 and 0.5 of 2.5 s
    assert "[cpu 50%, 4.00 GB/s, d2h 0.20 s at 5.00 GB/s]" in rendered
    assert "[cpu 25%]" in rendered  # machine_fetch: a quarter computing, the rest waiting


def test_build_breakdown_sums_and_renders_a_fit_programs_validation_slots():
    spans = synthetic_build_spans()
    program = next(s for s in spans if s["name"] == "device_program")
    for slots in (0, 48):
        attributes = {"program": "fleet_windowed_fit", "validation_slots": slots}
        spans.append(dict(program, attributes=attributes))
    found = build_breakdown(spans)
    parts = found["phases"]["cv_train"]["parts"]
    assert parts["program fleet_windowed_fit"] == {
        "seconds": 2.5, "count": 2, "validation_slots": 48,
    }
    assert "validation_slots" not in parts["program fleet_fit"]
    rendered = render_analysis(
        {"trace": "t", "spans_read": len(spans), "build_breakdown": found}
    )
    assert "  program fleet_windowed_fit [validation_slots=48]" in rendered


def test_build_breakdown_keeps_and_renders_a_dense_fits_shuffle_columns():
    """Beside ``validation_slots``: the widest row a bucket of the
    program shuffled (21 = 20 tags and the weight; 41 where a bucket's
    targets are an array of their own)."""
    spans = synthetic_build_spans()
    program = next(s for s in spans if s["name"] == "device_program")
    for columns in (21, 41):
        attributes = {
            "program": "fleet_fit", "validation_slots": 0, "shuffle_columns": columns,
        }
        spans.append(dict(program, attributes=attributes))
    found = build_breakdown(spans)
    part = found["phases"]["cv_train"]["parts"]["program fleet_fit"]
    assert part["shuffle_columns"] == 41 and part["validation_slots"] == 0
    rendered = render_analysis(
        {"trace": "t", "spans_read": len(spans), "build_breakdown": found}
    )
    assert "  program fleet_fit [validation_slots=0, shuffle_columns=41]" in rendered


def test_trace_cli_prints_the_part_table_under_each_phase(built, tmp_path):
    from click.testing import CliRunner

    from gordo_tpu.cli import gordo_tpu_cli

    _, spans, _ = built
    path = tmp_path / BUILD_TRACE_FILE
    path.write_text("".join(json.dumps(s) + "\n" for s in spans))
    result = CliRunner().invoke(gordo_tpu_cli, ["trace", str(path)])
    assert result.exit_code == 0, result.output
    lines = result.output.splitlines()
    at = next(i for i, line in enumerate(lines) if line.startswith("data_fetch"))
    assert sorted(line.split()[0] for line in lines[at + 1 : at + 6]) == [
        "machine_fetch", "pool_start", "provider_read", "resample_join", "row_filter",
    ]
    assert "0 of 2 machines fetched in processes" in lines[at]
    assert any(line.startswith("  program fleet_fit") for line in lines)
    assert any(line.startswith("compile path: trace_s=") for line in lines)
    as_json = CliRunner().invoke(gordo_tpu_cli, ["trace", str(path), "--as-json"])
    doc = json.loads(as_json.output)
    assert doc["build_breakdown"]["phases"]["dump"]["self_seconds"] >= 0.0
    assert render_analysis(doc).splitlines()[0].startswith("trace:")
