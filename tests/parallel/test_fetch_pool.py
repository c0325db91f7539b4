"""The fetch pool (``dataset/fetch_pool.py``): a fleet job's machines are
fetched in worker processes, and everything that counts or decides stays
on the builder's threads. This is the one module that starts workers
(``tests/conftest.py`` lifts the line out of every other test's reach);
it starts them once, with the line lowered to fleets of three."""

import hashlib
import json
import os
import pickle
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time
import types

import numpy as np
import pandas as pd
import pytest
import yaml
from click.testing import CliRunner

from gordo_tpu import telemetry
from gordo_tpu.cli import gordo_tpu_cli
from gordo_tpu.dataset import (
    GordoBaseDataset,
    InsufficientDataError,
    ListBackedDataProvider,
    RandomDataProvider,
    TimeSeriesDataset,
    fetch_pool,
)
from gordo_tpu.machine import Machine
from gordo_tpu.parallel import FleetBuilder
from gordo_tpu.telemetry.progress import load_status, render_status
from gordo_tpu.utils import faults
from gordo_tpu.utils.faults import FaultRule, inject

LINE = 3  # this module's MIN_MACHINES

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

DATASET = {
    "type": "TimeSeriesDataset",
    "train_start_date": "2020-01-01T00:00:00+00:00",
    "train_end_date": "2020-01-05T00:00:00+00:00",
}

#: a dataset whose class a fresh interpreter can import by name, because
#: its module lies on ``sys.path`` (the pool hands a worker the parent's):
#: it reports what the worker has imported
PROBE_MODULE = textwrap.dedent(
    '''
    import sys

    from gordo_tpu.dataset import TimeSeriesDataset


    class ProbeDataset(TimeSeriesDataset):
        def get_data(self):
            X, y = super().get_data()
            self._metadata["modules"] = sorted(sys.modules)
            self._metadata["pid"] = __import__("os").getpid()
            return X, y
    '''
)


class LocalDataset(TimeSeriesDataset):
    """Pickles here, by reference to a module that only this process
    has: it does not load in a worker."""


_only_here = types.ModuleType("only_in_the_test_process")
_only_here.LocalDataset = LocalDataset
LocalDataset.__module__ = _only_here.__name__
sys.modules[_only_here.__name__] = _only_here


class LockedProvider(RandomDataProvider):
    """A provider that holds what no pickle takes."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.lock = threading.Lock()


@pytest.fixture(scope="module", autouse=True)
def lowered_line(tmp_path_factory):
    """The line at fleets of three for this module, the probe's module on
    the path, and no worker left behind."""
    patch = pytest.MonkeyPatch()
    patch.setattr(fetch_pool, "MIN_MACHINES", LINE)
    probe_dir = tmp_path_factory.mktemp("probe")
    (probe_dir / "fetch_pool_probe.py").write_text(PROBE_MODULE)
    patch.syspath_prepend(str(probe_dir))
    fetch_pool.shutdown()
    yield
    fetch_pool.shutdown()
    patch.undo()


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def make_machine(name, tags=2, **dataset):
    return Machine.from_config(
        {
            "name": name,
            "model": MODEL,
            "dataset": {
                **DATASET,
                "tag_list": [f"{name}-t{i}" for i in range(tags)],
                **dataset,
            },
        },
        project_name="pool-test",
    )


def load(machines, **builder_kwargs):
    """``_load_all_data`` of ``machines`` under a recorder: the builder,
    every plan, the surviving ones and the ``build_part`` spans."""
    builder = FleetBuilder(machines, data_backoff=0, **builder_kwargs)
    builder.recorder = telemetry.SpanRecorder()
    plans = [builder._plan_machine(machine) for machine in machines]
    surviving = builder._load_all_data(plans)
    return builder, plans, surviving, builder.recorder.finished("build_part")


def parts(spans, part):
    return [s for s in spans if s["attributes"]["part"] == part]


def assert_same_frame(got, wanted):
    """The frame ``get_data()`` returned: dtypes, index with tz, unit and
    freq, column order, values to the bit."""
    pd.testing.assert_frame_equal(got, wanted, check_exact=True, check_freq=True)
    assert list(got.columns) == list(wanted.columns)
    assert got.dtypes.tolist() == wanted.dtypes.tolist()
    assert got.index.dtype == wanted.index.dtype  # tz and unit
    assert got.index.freq == wanted.index.freq
    assert got.index.name == wanted.index.name
    mine, theirs = got.to_numpy(), wanted.to_numpy()
    bits = f"u{theirs.dtype.itemsize}"
    assert np.array_equal(mine.view(bits), theirs.view(bits))
    # numpy's own dtype objects, not an unpickled copy of one: what is
    # computed from the frame pickles as it would have (module docstring)
    assert all(dtype is np.dtype(dtype.str) for dtype in got.dtypes)
    # and the block laid out as it was, where it was contiguous at all
    if theirs.flags.c_contiguous or theirs.flags.f_contiguous:
        assert mine.flags.c_contiguous == theirs.flags.c_contiguous
        assert mine.flags.f_contiguous == theirs.flags.f_contiguous


# -- the wire -------------------------------------------------------------------


@pytest.mark.parametrize(
    "payload",
    [
        {"plain": [1, 2.5, "three"]},
        np.arange(12.0).reshape(3, 4),
        np.asfortranarray(np.arange(12.0).reshape(3, 4)),
        np.zeros((0, 4)),
        pd.DataFrame(
            np.random.RandomState(0).rand(50, 3),
            index=pd.date_range("2020-01-01", periods=50, freq="10min", tz="Europe/Oslo"),
            columns=list("cab"),
        ),
    ],
    ids=["plain", "row-major", "column-major", "empty", "frame"],
)
def test_a_message_crosses_a_socket_with_its_buffers_out_of_band(payload):
    mine, theirs = socket.socketpair()
    buffers = []
    body = pickle.dumps(payload, protocol=5, buffer_callback=buffers.append)
    sender = threading.Thread(target=fetch_pool._send, args=(theirs, body, buffers))
    sender.start()
    got_body, got_buffers, nbytes = fetch_pool._recv(mine)
    sender.join(timeout=10.0)
    assert not sender.is_alive()
    assert bytes(got_body) == body
    assert [len(b) for b in got_buffers] == [b.raw().nbytes for b in buffers]
    assert nbytes == 16 + 8 * len(buffers) + len(body) + sum(map(len, got_buffers))
    got = pickle.loads(got_body, buffers=got_buffers)
    if isinstance(payload, pd.DataFrame):
        pd.testing.assert_frame_equal(got, payload, check_exact=True, check_freq=True)
        assert buffers  # the block went out of band
    elif isinstance(payload, np.ndarray):
        assert np.array_equal(got, payload) and got.flags.writeable
        assert got.flags["F_CONTIGUOUS"] == payload.flags["F_CONTIGUOUS"]
    else:
        assert got == payload
    mine.close()
    theirs.close()


def test_a_closed_socket_reads_as_end_of_file():
    mine, theirs = socket.socketpair()
    theirs.sendall(b"\x01\x02\x03")  # less than a header
    theirs.close()
    with pytest.raises(EOFError):
        fetch_pool._recv(mine)
    mine.close()


def _frame(values, columns=("a", "b"), freq="10min"):
    return pd.DataFrame(
        values,
        index=pd.date_range("2020-01-01", periods=len(values), freq=freq, tz="UTC"),
        columns=list(columns),
    )


@pytest.mark.parametrize(
    "other, again",
    [
        (lambda X: X.copy(), True),
        (lambda X: X[["a", "b"]], True),
        (lambda X: X[["b", "a"]], False),  # another column order
        (lambda X: X[["a"]], False),
        (lambda X: X * 1.0000001, False),
        (lambda X: X.mask(X == 0.0, -0.0), False),  # equal, not to the bit
        (lambda X: X.astype(np.float32), False),
        (lambda X: X.iloc[:-1], False),
        (lambda X: X.set_axis(X.index._with_freq(None)), False),  # freq is part of it
        (lambda X: X.to_numpy(), False),
    ],
    ids=[
        "copy", "same-columns", "reordered", "subset", "other-values", "minus-zero",
        "other-dtype", "fewer-rows", "no-freq", "no-frame",
    ],
)
def test_y_crosses_once_only_where_it_is_x_again(other, again):
    X = _frame(np.array([[0.0, 1.0], [2.0, np.nan], [4.0, 5.0]]))
    assert fetch_pool._is_again(X, other(X)) is again


def test_frames_of_one_width_and_other_dtypes_are_not_each_other():
    floats = _frame(np.zeros((3, 2)))
    assert not fetch_pool._is_again(floats, floats.astype(np.int64))
    mixed = floats.assign(b=[1, 2, 3])
    assert fetch_pool._block(mixed) is None  # two blocks: pickled as it is
    assert fetch_pool._pack(mixed) is mixed and fetch_pool._unpack(mixed) is mixed
    assert not fetch_pool._is_again(mixed, mixed.copy())
    # the same bits under another memory order are another frame to stage
    row_major = pd.DataFrame(
        np.ascontiguousarray(floats.to_numpy()), index=floats.index, columns=floats.columns,
        copy=False,
    )
    assert floats.to_numpy().strides != row_major.to_numpy().strides
    assert not fetch_pool._is_again(floats, row_major)


@pytest.mark.parametrize("order", ["C", "F", "reversed"])
@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.int64, np.bool_])
def test_a_one_dtype_frame_crosses_as_its_parts_and_comes_back_whole(order, dtype):
    values = (np.random.RandomState(1).rand(7, 3) * 9).astype(dtype)
    values = {"C": np.ascontiguousarray, "F": np.asfortranarray, "reversed": lambda v: v[:, ::-1]}[
        order
    ](values)
    frame = pd.DataFrame(
        values,
        index=pd.date_range("2020-03-01", periods=7, freq="1h", tz="Asia/Tokyo", unit="ms"),
        columns=["z", "a", "m"],
        copy=False,
    )
    buffers = []
    body = pickle.dumps(fetch_pool._pack(frame), protocol=5, buffer_callback=buffers.append)
    received = [bytearray(b.raw()) for b in buffers]
    packed = pickle.loads(body, buffers=received)
    back, clone = fetch_pool._unpack(packed), fetch_pool._unpack(packed, copy=True)
    assert_same_frame(back, frame)
    assert_same_frame(clone, frame)
    assert back.index.dtype == frame.index.dtype and str(back.index.dtype) == "datetime64[ms, Asia/Tokyo]"
    assert np.shares_memory(back.to_numpy(), np.frombuffer(received[0], np.uint8))  # no copy
    assert not np.shares_memory(back.to_numpy(), clone.to_numpy())


# -- which path ------------------------------------------------------------------


@pytest.mark.parametrize(
    "machines, workers_up, pooled",
    [
        (1, 0, False), (1, 4, False),  # one machine: nothing to run beside it
        (2, 0, False), (LINE - 1, 0, False),  # under the line, no pool: no start
        (2, 4, True), (LINE - 1, 1, True),  # the pool is up: no reason to leave it idle
        (LINE, 0, True), (160, 0, True), (160, 12, True),
    ],
)
def test_the_path_is_read_off_the_job(monkeypatch, machines, workers_up, pooled):
    monkeypatch.setattr(fetch_pool, "size", lambda: workers_up)
    assert fetch_pool.wanted(machines) is pooled


def test_the_line_as_it_ships_has_lstm_builds_sixteen_on_the_pools_side(monkeypatch):
    monkeypatch.setattr(fetch_pool, "MIN_MACHINES", _SHIPPED_LINE)
    monkeypatch.setattr(fetch_pool, "size", lambda: 0)
    assert fetch_pool.wanted(16) and not fetch_pool.wanted(2)
    assert 2 < _SHIPPED_LINE <= 16


#: read at import, before the module's fixture lowers it
_SHIPPED_LINE = fetch_pool.MIN_MACHINES


@pytest.mark.parametrize(
    "data_workers, machines, cores, workers",
    [(16, 160, 13, 12), (16, 64, 30, 16), (16, 16, 13, 12), (16, 3, 13, 3), (4, 160, 13, 4),
     (16, 160, 1, 1)],
)
def test_workers_are_the_fetches_in_flight_the_machines_and_the_cores_but_one(
    monkeypatch, data_workers, machines, cores, workers
):
    monkeypatch.setattr(fetch_pool, "cores", lambda: cores)
    assert fetch_pool.workers_for(data_workers, machines) == workers


def _series(n):
    index = pd.date_range("2020-01-01", periods=n, freq="1min", tz="UTC")
    return [pd.Series(np.arange(n, dtype=float), index=index, name=f"t{i}") for i in range(2)]


def _dataset(**kwargs):
    return TimeSeriesDataset(
        **{
            "train_start_date": DATASET["train_start_date"],
            "train_end_date": DATASET["train_end_date"],
            "tag_list": ["t0", "t1"],
            **kwargs,
        }
    )


class NotATimeSeries(GordoBaseDataset):
    def get_data(self):
        return None, None

    def get_metadata(self):
        return {}


@pytest.mark.parametrize(
    "dataset, crosses",
    [
        (lambda: _dataset(), True),
        (lambda: _dataset(data_provider=ListBackedDataProvider(_series(100))), True),
        # a provider that carries its data: the copy would cost what the worker saves
        (lambda: _dataset(data_provider=ListBackedDataProvider(_series(200_000))), False),
        (lambda: _dataset(data_provider=LockedProvider()), False),
        (lambda: NotATimeSeries(), False),
    ],
    ids=["named-source", "small-list", "carries-its-data", "does-not-pickle", "other-class"],
)
def test_a_dataset_crosses_only_if_it_pickles_small(dataset, crosses):
    request = fetch_pool.crossing(dataset())
    assert (request is not None) is crosses
    if crosses:
        assert len(request) <= fetch_pool.CROSSING_LIMIT_BYTES
        assert isinstance(pickle.loads(request), TimeSeriesDataset)


# -- what comes back ---------------------------------------------------------------


@pytest.fixture(scope="module")
def workers(lowered_line):
    fetch_pool.ensure(2)
    assert fetch_pool.size() == 2
    return fetch_pool


VARIANTS = {
    "targets-are-the-tags": {},
    "three-tags-utc": {"tag_list": ["a", "b", "c"]},
    "row-filter-drops-the-freq": {"row_filter": "`t0` > -1000 and `t1` < 30"},
    "targets-a-subset": {"target_tag_list": ["t1"]},
    "targets-reordered": {"tag_list": ["t0", "t1", "t2"], "target_tag_list": ["t2", "t0"]},
    "another-timezone": {
        "train_start_date": "2020-01-01T00:00:00+01:00",
        "train_end_date": "2020-01-21T00:00:00+01:00",
    },
    "two-aggregations": {"aggregation_methods": ["mean", "max"]},
    "hourly": {"resolution": "1h"},
    "ffill": {"interpolation_method": "ffill"},
    "filtered-periods": {
        "known_filter_periods": [["2020-01-02T00:00:00+00:00", "2020-01-02T12:00:00+00:00"]]
    },
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_a_fetch_comes_back_as_the_frames_get_data_returned(workers, variant):
    kwargs = {
        "train_end_date": "2020-01-21T00:00:00+00:00",
        "data_provider": RandomDataProvider(min_size=3000, max_size=4000),
        **VARIANTS[variant],
    }
    here = _dataset(**kwargs)
    there = _dataset(**kwargs)
    there.fetch_cpu_timed = True
    X, y = here.get_data()
    fetched = workers.fetch(workers.crossing(there))
    assert_same_frame(fetched.X, X)
    assert_same_frame(fetched.y, y)
    assert fetched.y is not fetched.X
    assert fetched.state["_metadata"] == here.get_metadata()
    assert json.dumps(fetched.state["_metadata"], sort_keys=True) == json.dumps(
        here.get_metadata(), sort_keys=True
    )  # floats to the bit
    assert set(fetched.state["fetch_seconds"]) == {"provider_read", "resample_join", "row_filter"}
    assert set(fetched.state["fetch_cpu_seconds"]) == set(fetched.state["fetch_seconds"])
    assert 0.0 < sum(fetched.state["fetch_seconds"].values()) <= fetched.seconds
    assert 0.0 <= fetched.cpu_seconds <= fetched.seconds + 0.05
    # y's values cross once where y is X again: the targets are the tags
    once = "target_tag_list" not in VARIANTS[variant]
    both = X.to_numpy().nbytes + y.to_numpy().nbytes
    assert (fetched.nbytes < X.to_numpy().nbytes + X.index.nbytes + y.to_numpy().nbytes // 2) is once
    assert once or fetched.nbytes > both


def test_many_threads_over_few_workers_each_get_their_own_answer(workers):
    """More threads than workers, and than cores, switching often: a
    worker answers one request at a time and each thread reads the
    answer to its own."""
    import concurrent.futures

    names = [f"stress-{i}" for i in range(48)]

    def one(name):
        dataset = _dataset(tag_list=[f"{name}-a", f"{name}-b"])
        fetched = workers.fetch(workers.crossing(dataset))
        return name, list(fetched.X.columns), fetched.state["_metadata"]["tag_list"]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with concurrent.futures.ThreadPoolExecutor(32) as executor:
            answers = list(executor.map(one, names, timeout=120.0))
    finally:
        sys.setswitchinterval(interval)
    for name, columns, tags in answers:
        assert columns == [f"{name}-a", f"{name}-b"]
        assert [tag["name"] for tag in tags] == columns
    assert workers.size() == 2 and fetch_pool._idle.qsize() == 2  # all back in the pool


def test_a_worker_raises_in_the_parent_as_the_same_type_with_its_traceback(workers):
    short = _dataset(n_samples_threshold=10**9)
    with pytest.raises(InsufficientDataError, match="below threshold") as raised:
        workers.fetch(workers.crossing(short))
    assert type(raised.value) is InsufficientDataError
    (note,) = raised.value.__notes__
    assert note.startswith("in a fetch worker:") and "get_data" in note
    assert workers.size() == 2  # the worker is well and back in the pool


def test_an_exception_that_does_not_pickle_still_says_what_it_was():
    class Local(Exception):
        pass

    portable = fetch_pool._portable(Local("so it went"))
    assert type(portable) is RuntimeError and str(portable) == "Local: so it went"
    assert pickle.loads(pickle.dumps(portable)).__notes__ == portable.__notes__


def test_a_dataset_that_does_not_load_in_a_worker_cannot_cross(workers):
    local = LocalDataset(
        train_start_date=DATASET["train_start_date"],
        train_end_date=DATASET["train_end_date"],
        tag_list=["t0"],
    )
    request = workers.crossing(local)
    assert request is not None  # it pickles here, by reference
    with pytest.raises(fetch_pool.CannotCross):
        workers.fetch(request)
    assert workers.size() == 2


def test_no_worker_has_jax_or_sklearn(workers):
    from fetch_pool_probe import ProbeDataset

    seen = {}
    for _ in range(8):  # both workers, most likely; each at least proves itself
        probe = ProbeDataset(
            train_start_date=DATASET["train_start_date"],
            train_end_date=DATASET["train_end_date"],
            tag_list=["p0", "p1"],
        )
        state = workers.fetch(workers.crossing(probe)).state["_metadata"]
        seen[state["pid"]] = state["modules"]
    assert os.getpid() not in seen
    for modules in seen.values():
        loaded = {name.split(".")[0] for name in modules}
        assert "pandas" in loaded and "gordo_tpu" in loaded
        assert not loaded & {"jax", "jaxlib", "sklearn", "scipy", "flax", "optax"}
        assert "gordo_tpu.serializer" not in modules
        assert "gordo_tpu.parallel" not in modules


def test_the_dataset_layer_imports_without_sklearn_or_jax():
    """What a worker pays at its start: a fresh interpreter importing the
    pool's module."""
    code = (
        "import sys, gordo_tpu.dataset.fetch_pool; "
        "print(sorted({m.split('.')[0] for m in sys.modules} & "
        "{'jax', 'jaxlib', 'sklearn', 'scipy'}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONPATH": os.pathsep.join(p for p in sys.path if p)},
    )
    assert out.stdout.strip() == "[]"


# -- the builder over the pool -------------------------------------------------------


def test_a_fleet_over_the_line_is_fetched_in_processes_bit_for_bit(workers):
    names = [f"over-{i}" for i in range(LINE + 1)]
    builder, plans, surviving, spans = load([make_machine(n) for n in names])
    assert [p.machine.name for p in surviving] == names and not builder.build_errors
    fetches = parts(spans, "machine_fetch")
    assert sorted(s["attributes"]["machine"] for s in fetches) == names
    for span in fetches:
        attributes = span["attributes"]
        assert attributes["worker"] == "process"
        assert attributes["bytes"] > attributes["rows"] * 2 * 8
        assert attributes["retries"] == 0
        # the worker's seconds and CPU seconds for the call, the dataset's
        # parts inside them
        nested = telemetry.nested_part_seconds(attributes)
        assert set(nested) == {"provider_read", "resample_join", "row_filter"}
        assert 0.0 < sum(nested.values()) <= span["duration_ms"] / 1000.0
        assert set(telemetry.nested_part_cpu_seconds(attributes)) == set(nested)
        assert 0.0 <= attributes["cpu_seconds"] <= span["duration_ms"] / 1000.0 + 0.05
    (start,) = parts(spans, "pool_start")
    # the job may keep LINE + 1 workers busy and found two up
    wanted = fetch_pool.workers_for(builder.data_workers, len(names))
    assert start["attributes"]["count"] == max(0, wanted - 2)
    assert workers.size() == max(2, wanted)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fetch_pool, "wanted", lambda machines: False)
        _, threaded, _, thread_spans = load([make_machine(n) for n in names])
    assert {s["attributes"]["worker"] for s in parts(thread_spans, "machine_fetch")} == {"thread"}
    assert all("bytes" not in s["attributes"] for s in parts(thread_spans, "machine_fetch"))
    for pooled, plain in zip(plans, threaded):
        assert_same_frame(pooled.X, plain.X)
        assert_same_frame(pooled.y, plain.y)
        assert pooled.dataset.get_metadata() == plain.dataset.get_metadata()
        assert np.array_equal(pooled.X_arr, plain.X_arr) and pooled.X_arr.dtype == plain.X_arr.dtype
        assert np.array_equal(pooled.y_arr, plain.y_arr)
        assert pooled.n_windows == plain.n_windows and pooled.query_duration > 0.0


def test_a_job_of_one_machine_starts_nothing_and_asks_no_worker():
    fetch_pool.shutdown()
    builder, plans, surviving, spans = load([make_machine("alone")])
    assert fetch_pool.size() == 0
    assert len(surviving) == 1
    (fetch,) = parts(spans, "machine_fetch")
    assert fetch["attributes"]["worker"] == "thread" and "bytes" not in fetch["attributes"]
    (start,) = parts(spans, "pool_start")
    assert start["attributes"]["count"] == 0 and start["duration_ms"] < 50.0


def test_the_first_job_over_the_line_starts_the_pool_and_the_next_finds_it_up():
    fetch_pool.shutdown()
    cpu_before = FleetBuilder._process_cpu_clock()
    names = [f"first-{i}" for i in range(LINE)]
    _, _, surviving, spans = load([make_machine(n) for n in names], data_workers=2)
    (start,) = parts(spans, "pool_start")
    assert start["attributes"]["count"] == fetch_pool.size() == fetch_pool.workers_for(2, LINE)
    assert start["attributes"]["cpu_seconds"] > 0.0  # the workers' imports
    assert len(surviving) == LINE
    # the workers' CPU seconds are in the builder's process clock
    assert FleetBuilder._process_cpu_clock() - cpu_before >= start["attributes"]["cpu_seconds"]
    _, _, _, again = load([make_machine(n + "-again") for n in names], data_workers=2)
    (start,) = parts(again, "pool_start")
    assert start["attributes"]["count"] == 0 and start["attributes"]["cpu_seconds"] == 0.0
    # and a job of two, under the line, has no reason to leave it idle
    _, _, _, two = load([make_machine("two-a"), make_machine("two-b")])
    assert {s["attributes"]["worker"] for s in parts(two, "machine_fetch")} == {"process"}


def test_a_dataset_that_cannot_cross_is_fetched_on_the_thread_and_counted(workers):
    machines = [make_machine(f"mixed-{i}") for i in range(LINE)]
    locked = make_machine("mixed-locked")
    locked.dataset = TimeSeriesDataset(
        train_start_date=DATASET["train_start_date"],
        train_end_date=DATASET["train_end_date"],
        tag_list=["l0", "l1"],
        data_provider=LockedProvider(),
    )
    local = make_machine("mixed-local")
    local.dataset = LocalDataset(
        train_start_date=DATASET["train_start_date"],
        train_end_date=DATASET["train_end_date"],
        tag_list=["l2", "l3"],
    )
    builder, plans, surviving, spans = load(machines + [locked, local])
    assert len(surviving) == LINE + 2 and not builder.build_errors
    where = {s["attributes"]["machine"]: s["attributes"] for s in parts(spans, "machine_fetch")}
    assert where["mixed-locked"]["worker"] == where["mixed-local"]["worker"] == "thread"
    assert {where[m.name]["worker"] for m in machines} == {"process"}
    assert where["mixed-local"]["retries"] == 0  # the fall to the thread is no retry
    assert sum(telemetry.part_sums(a)["in_process"] for a in where.values()) == LINE
    for plan in plans:
        assert plan.X is not None and plan.dataset.get_metadata()["row_count"] == len(plan.X)


def test_a_fault_rule_retries_twice_and_counts_twice_with_the_pool_in_use(workers):
    machines = [make_machine("flaky-m")] + [make_machine(f"steady-{i}") for i in range(LINE)]
    rule = FaultRule("data_fetch", match="flaky-*", times=2)
    with inject(rule):
        builder, plans, surviving, spans = load(machines, data_retries=2)
    assert len(surviving) == len(machines) and not builder.build_errors
    assert rule.fired == 2  # counted in this process, where the rule lives
    assert builder.robustness["data_fetch_retries"] == 2
    by_name = {p.machine.name: p for p in plans}
    assert by_name["flaky-m"].data_retries == 2
    where = {s["attributes"]["machine"]: s["attributes"] for s in parts(spans, "machine_fetch")}
    assert where["flaky-m"]["retries"] == 2 and where["flaky-m"]["worker"] == "process"
    assert all(where[f"steady-{i}"]["retries"] == 0 for i in range(LINE))


def test_a_fault_rule_that_never_ends_fails_that_machine_alone(workers):
    machines = [make_machine("dead-m")] + [make_machine(f"live-{i}") for i in range(LINE)]
    with inject(FaultRule("data_fetch", match="dead-*", times=None)):
        builder, _, surviving, spans = load(machines, data_retries=1)
    assert sorted(p.machine.name for p in surviving) == [f"live-{i}" for i in range(LINE)]
    assert isinstance(builder.build_errors["dead-m"], faults.FaultInjected)
    assert len(parts(spans, "machine_fetch")) == len(machines)


def test_a_workers_insufficient_data_fails_that_machine_alone_and_is_not_retried(workers):
    machines = [make_machine(f"enough-{i}") for i in range(LINE)]
    machines.append(make_machine("too-few", n_samples_threshold=10**9))
    builder, plans, surviving, spans = load(machines, data_retries=2)
    assert sorted(p.machine.name for p in surviving) == [f"enough-{i}" for i in range(LINE)]
    assert set(builder.build_errors) == {"too-few"}
    assert type(builder.build_errors["too-few"]) is InsufficientDataError
    assert builder.robustness["data_fetch_retries"] == 0
    (failed,) = [s for s in parts(spans, "machine_fetch") if s["attributes"]["machine"] == "too-few"]
    assert failed["attributes"]["worker"] == "process" and failed["attributes"]["retries"] == 0
    assert "rows" not in failed["attributes"]
    assert workers.size() >= 2  # a dataset's error costs no worker


def test_a_worker_that_dies_is_dropped_and_its_fetch_tried_on_another(workers):
    workers.ensure(3)
    before = workers.size()
    victim = fetch_pool._workers[0]
    victim.process.kill()
    victim.process.wait()
    machines = [make_machine(f"after-{i}") for i in range(2 * before)]
    # as many fetches in flight as workers: the job starts none
    builder, _, surviving, spans = load(machines, data_retries=2, data_workers=before)
    assert len(surviving) == len(machines) and not builder.build_errors
    assert workers.size() == before - 1
    assert builder.robustness["data_fetch_retries"] == 1
    assert {s["attributes"]["worker"] for s in parts(spans, "machine_fetch")} == {"process"}


def test_with_no_worker_left_a_fetch_cannot_cross_and_the_thread_computes():
    fetch_pool.shutdown()
    request = fetch_pool.crossing(_dataset())
    with pytest.raises(fetch_pool.CannotCross):
        fetch_pool.fetch(request)


def test_where_no_worker_can_be_started_the_job_is_fetched_on_its_threads(monkeypatch):
    fetch_pool.shutdown()
    monkeypatch.setattr(sys, "executable", "/nonexistent/python")
    names = [f"unstarted-{i}" for i in range(LINE)]
    builder, _, surviving, spans = load([make_machine(n) for n in names])
    assert fetch_pool.size() == 0
    assert len(surviving) == LINE and not builder.build_errors
    assert builder.robustness["data_fetch_retries"] == 0  # a fall to the thread is no retry
    assert {s["attributes"]["worker"] for s in parts(spans, "machine_fetch")} == {"thread"}
    (start,) = parts(spans, "pool_start")
    assert start["attributes"]["count"] == 0


def test_the_serializer_knows_the_resolver_by_its_old_name():
    from gordo_tpu.serializer import import_utils as old_name
    from gordo_tpu.utils import import_utils

    assert old_name.import_location is import_utils.import_location
    assert (
        old_name.prepare_back_compatible_locations
        is import_utils.prepare_back_compatible_locations
    )


def test_workers_end_when_their_parent_is_killed_outright(tmp_path):
    """The benchmark's child can be killed outright: a worker reads the end
    of its socket and exits, with nothing in the parent to tell it."""
    code = textwrap.dedent(
        """
        import sys, time
        from gordo_tpu.dataset import fetch_pool
        fetch_pool.ensure(2)
        print(" ".join(str(w.process.pid) for w in fetch_pool._workers), flush=True)
        time.sleep(60)
        """
    )
    parent = subprocess.Popen(
        [sys.executable, "-c", code], stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": os.pathsep.join(p for p in sys.path if p)},
    )
    pids = [int(pid) for pid in parent.stdout.readline().split()]
    assert len(pids) == 2 and all(_alive(pid) for pid in pids)
    parent.send_signal(signal.SIGKILL)
    parent.wait(timeout=10.0)
    parent.stdout.close()
    deadline = time.monotonic() + 10.0
    while any(_alive(pid) for pid in pids) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not any(_alive(pid) for pid in pids)


def _alive(pid):
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


# -- two jobs of one process, as the command runs them -------------------------------


def run_job(root, job, names):
    config = root / f"{job}.yaml"
    config.write_text(
        yaml.safe_dump(
            {
                "machines": [
                    {
                        "name": name,
                        "model": MODEL,
                        "dataset": {**DATASET, "tag_list": [f"{name}-{t}" for t in range(2)]},
                    }
                    for name in names
                ]
            }
        )
    )
    out = root / job
    result = CliRunner().invoke(
        gordo_tpu_cli, ["build-fleet", str(config), str(out)], catch_exceptions=False
    )
    assert result.exit_code == 0, result.output
    return out


def md5s(out, names):
    found = {}
    for name in names:
        with open(out / name / "model.pkl", "rb") as f:
            found[name] = hashlib.md5(f.read()).hexdigest()
    return found


def test_two_build_fleet_jobs_of_one_process_and_what_they_print(tmp_path):
    fetch_pool.shutdown()
    names = [f"job-{i}" for i in range(LINE)]
    first = run_job(tmp_path, "first", names)
    second = run_job(tmp_path, "second", names)
    assert md5s(first, names) == md5s(second, names)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fetch_pool, "wanted", lambda machines: False)
        threaded = run_job(tmp_path, "threaded", names)
    # the artifacts are the in-thread path's
    assert md5s(threaded, names) == md5s(first, names)

    statuses = {job: load_status(str(out)) for job, out in
                (("first", first), ("second", second), ("threaded", threaded))}
    fetch_parts = {job: s["phases"]["data_fetch"]["parts"] for job, s in statuses.items()}
    assert fetch_parts["first"]["pool_start"]["count"] == fetch_pool.size() > 0
    assert fetch_parts["first"]["pool_start"]["seconds"] > 0.1
    assert fetch_parts["second"]["pool_start"]["count"] == 0
    assert fetch_parts["threaded"]["pool_start"]["count"] == 0
    for job in ("first", "second"):
        fetched = fetch_parts[job]["machine_fetch"]
        assert (fetched["count"], fetched["in_process"]) == (LINE, LINE)
        assert fetched["bytes"] > 0 and fetched["cpu_seconds"] >= 0.0
        for part in ("provider_read", "resample_join", "row_filter"):
            assert fetch_parts[job][part]["count"] == LINE
            assert "cpu_seconds" in fetch_parts[job][part]
        assert f"{LINE} of {LINE} machines fetched in processes" in render_status(statuses[job])
    assert fetch_parts["threaded"]["machine_fetch"]["in_process"] == 0
    assert "bytes" not in fetch_parts["threaded"]["machine_fetch"]
    assert f"0 of {LINE} machines fetched in processes" in render_status(statuses["threaded"])
    # the first job's phase holds its workers' start in its process CPU
    first_phase = statuses["first"]["phases"]["data_fetch"]
    assert first_phase["process_cpu_seconds"] >= fetch_parts["first"]["pool_start"]["cpu_seconds"] > 0

    trace = CliRunner().invoke(gordo_tpu_cli, ["trace", str(first / "build_trace.jsonl")])
    assert trace.exit_code == 0, trace.output
    line = next(l for l in trace.output.splitlines() if l.startswith("data_fetch"))
    assert f"{LINE} of {LINE} machines fetched in processes" in line
    as_json = CliRunner().invoke(
        gordo_tpu_cli, ["trace", str(first / "build_trace.jsonl"), "--as-json"]
    )
    breakdown = json.loads(as_json.output)["build_breakdown"]["phases"]["data_fetch"]["parts"]
    assert breakdown["machine_fetch"]["in_process"] == LINE
    assert breakdown["pool_start"]["count"] == fetch_parts["first"]["pool_start"]["count"]
