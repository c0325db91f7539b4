"""A large artifact's parameters cross the link while its ``model.pkl``
is hashed and written: the final fit's ``collect`` starts every leaf on
its way and waits for none (``fleet.defers``, models/in_flight.py), and
the pickler inside ``serializer.dump`` takes each when it reaches it.
The oracle is the same build on the eager schedule: the line that
decides (``fleet.DEFER_MIN_MEMBER_LEAF_BYTES``) is moved by the tests,
as ``fetch_pool.MIN_MACHINES`` is."""

import json
import os
import pickletools

import jax
import numpy as np
import pytest

from gordo_tpu import serializer
from gordo_tpu.builder.build_model import ModelBuilder
from gordo_tpu.models import in_flight
from gordo_tpu.models.training import split_fit_kwargs
from gordo_tpu.parallel import FleetBuilder, fleet
from gordo_tpu.parallel.journal import BuildJournal
from gordo_tpu.telemetry.progress import BUILD_STATUS_FILE, render_status
from gordo_tpu.telemetry.trace_analysis import build_breakdown, render_analysis
from gordo_tpu.utils.faults import InjectedDeviceError

from .test_fleet_faults import staging_dirs
from .test_fold_params_on_device import artifact_md5, attributes, build, machine

NAMES = ["bb-a", "bb-b"]
#: the toy backbone's leaves of 4 KiB or more are fetched on their own
#: (read-only, as a backbone's expert weights are) and the rest are
#: coalesced copies, on both schedules: a pickle tells the two apart
OWN_FETCH_BYTES = 4 << 10


def estimator_of(model):
    return model.base_estimator.steps[-1][1]


def status_parts(out, phase):
    with open(os.path.join(str(out), BUILD_STATUS_FILE)) as f:
        return json.load(f)["phases"][phase].get("parts", {})


class Watched:
    """The transfers in the order they were started and in the order
    they were first waited for."""

    def __init__(self, patch):
        self.started, self.taken = [], []
        #: at every fit's start (a bucket's parameters are made): how
        #: many transfers had been started, and how many of them still
        #: held their leaf on the device
        self.at_a_fit, self.transfers = [], []
        start, host = in_flight._Transfer.__init__, in_flight._Transfer.host
        init = fleet.FleetTrainer._init_bucket_params

        def started(transfer, flight, leaf, writable):
            self.started.append((id(flight), id(transfer), int(leaf.nbytes), writable))
            self.transfers.append(transfer)
            start(transfer, flight, leaf, writable)

        def a_fit(trainer, *args):
            self.at_a_fit.append(self.on_the_device())
            return init(trainer, *args)

        def taken(transfer):
            if id(transfer) not in self.taken:  # list.append is atomic
                self.taken.append(id(transfer))
            return host(transfer)

        patch.setattr(in_flight._Transfer, "__init__", started)
        patch.setattr(in_flight._Transfer, "host", taken)
        patch.setattr(fleet.FleetTrainer, "_init_bucket_params", a_fit)

    def on_the_device(self):
        return len(self.transfers), sum(t._device is not None for t in self.transfers)


@pytest.fixture(scope="module")
def both_schedules(tmp_path_factory):
    """Two toy backbones (a backbone trains alone: a fit and a flight
    each) built twice: with the line where no member reaches it, and
    with the line at one byte."""
    root = tmp_path_factory.mktemp("in-flight")
    built = {}
    for schedule, line in (("eager", 1 << 62), ("deferred", 1)):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(fleet, "_COALESCE_MAX_LEAF_BYTES", OWN_FETCH_BYTES)
            patch.setattr(fleet, "DEFER_MIN_MEMBER_LEAF_BYTES", line)
            watched = Watched(patch)
            built[schedule] = (
                *build([machine("backbone", n) for n in NAMES], root / schedule),
                watched,
            )
    return root, built


def test_model_pkl_is_the_eager_schedules_byte_for_byte(both_schedules):
    root, built = both_schedules
    for schedule in built:
        assert not built[schedule][0].build_errors
    for name in NAMES:
        md5 = artifact_md5(root / "deferred", name)
        assert md5 == artifact_md5(root / "eager", name)
        for schedule in built:
            info = serializer.load_info(str(root / schedule / name))
            assert info["checksum"] == md5
    # both kinds of leaf are in it: a read-only one's bytes, a copy's
    with open(root / "deferred" / NAMES[0] / "model.pkl", "rb") as f:
        written = {op.name for op, _, _ in pickletools.genops(f)}
    assert "BYTEARRAY8" in written and "BINBYTES" in written


def test_the_loaded_model_predicts_the_same(both_schedules):
    root, built = both_schedules
    rows = np.random.RandomState(0).rand(130, 4).astype(np.float32)
    for name in NAMES:
        eager = serializer.load(str(root / "eager" / name))
        deferred = serializer.load(str(root / "deferred" / name))
        predicted = deferred.predict(rows)
        assert predicted.shape[0] == 30 and np.isfinite(predicted).all()
        np.testing.assert_array_equal(predicted, eager.predict(rows))


def test_what_build_returns_holds_plain_numpy_parameters(both_schedules):
    _, built = both_schedules
    eager, deferred = (built[s][1] for s in ("eager", "deferred"))
    assert [made.name for _, made in deferred] == NAMES
    for (model, _), (eager_model, _) in zip(deferred, eager):
        leaves = jax.tree_util.tree_leaves(estimator_of(model).params_)
        expected = jax.tree_util.tree_leaves(estimator_of(eager_model).params_)
        assert len(leaves) == len(expected) > 10
        for leaf, other in zip(leaves, expected):
            assert type(leaf) is np.ndarray
            assert leaf.flags.writeable == other.flags.writeable
            np.testing.assert_array_equal(leaf, other)


def test_the_spans_say_what_was_deferred_and_what_the_pickler_found(both_schedules):
    root, built = both_schedules
    _, results, spans, watched = built["deferred"]
    size = sum(nbytes for _, _, nbytes, _ in watched.started)
    assert size > 0 and {w for _, _, _, w in watched.started} == {True, False}
    # the last fit's leaves are the dump's to find: the first fit's were
    # waited for before the second ran (a ``collect`` of seconds alone)
    last = sum(n for f, _, n, _ in watched.started if f == watched.started[-1][0])
    assert 0 < last < size
    collects = attributes(spans, "build_part", part="collect", phase="final_fit")
    landing = [c for c in collects if "bytes" not in c]
    collects = [c for c in collects if "bytes" in c]
    assert len(landing) == 1 and landing[0]["bytes_deferred"] == 0
    assert len(collects) == 2 and sum(c["bytes_deferred"] for c in collects) == size
    for collect in collects:  # the histories alone came back in it
        assert 0 < collect["bytes"] < 100 and collect["d2h_seconds"] >= 0.0
    (write,) = attributes(spans, "build_part", part="write", phase="dump")
    assert write["bytes_fetched_beside_write"] == last < write["bytes"]
    assert write["fetch_wait_seconds"] >= 0.0
    (fetched,) = attributes(spans, "build_part", part="collect", phase="dump")
    assert fetched["bytes"] == last and fetched["count"] == 1
    assert fetched["d2h_seconds"] == write["fetch_wait_seconds"]
    # build_status.json carries them, and the two commands print them
    assert status_parts(root / "deferred", "final_fit")["collect"]["bytes_deferred"] == size
    dump = status_parts(root / "deferred", "dump")
    assert dump["write"]["bytes_fetched_beside_write"] == dump["collect"]["bytes"] == last
    assert 0.0 <= dump["write"]["fetch_wait_seconds"] <= dump["write"]["seconds"]
    with open(root / "deferred" / BUILD_STATUS_FILE) as f:
        status = render_status(json.load(f))
    found = build_breakdown(spans)
    assert found["phases"]["dump"]["parts"]["write"]["bytes_fetched_beside_write"] == last
    trace = render_analysis({"trace": "t", "spans_read": len(spans), "build_breakdown": found})
    for text in (status, trace):
        assert "GB on their way" in text
        assert "GB fetched beside, waited 0.0" in text

    # the eager schedule says it deferred nothing, and has no fetch in its dump
    _, _, eager_spans, eager_watched = built["eager"]
    assert eager_watched.started == []
    collects = attributes(eager_spans, "build_part", part="collect", phase="final_fit")
    assert [c["bytes_deferred"] for c in collects] == [0, 0]  # and no wait between
    assert sum(c["bytes"] for c in collects) > size
    (write,) = attributes(eager_spans, "build_part", part="write", phase="dump")
    assert write["bytes_fetched_beside_write"] == 0 == write["fetch_wait_seconds"]
    assert not attributes(eager_spans, "build_part", part="collect", phase="dump")


def test_the_pickler_takes_the_leaves_in_the_order_collect_started_them(both_schedules):
    """Each artifact's pickler meets its fit's transfers first to last
    (the two artifacts are written by two threads at once)."""
    _, built = both_schedules
    watched = built["deferred"][3]
    flights = {flight for flight, _, _, _ in watched.started}
    assert len(flights) == 2 and len(watched.taken) == len(watched.started) > 20
    for flight in flights:
        started = [t for f, t, _, _ in watched.started if f == flight]
        assert [t for t in watched.taken if t in started] == started


def test_no_fit_runs_beside_an_earlier_fits_parameters(both_schedules):
    """A flight holds its fit's block on the device: the second
    backbone's fit starts when the first one's leaves are all on the
    host, as on the eager schedule, and only the last fit's flight is
    aloft when the dump begins."""
    _, built = both_schedules
    watched = built["deferred"][3]
    flights = [flight for flight, _, _, _ in watched.started]
    first = flights.count(flights[0])
    assert 0 < first < len(flights)
    # the CV fits and the first final fit, then the second final fit
    assert watched.at_a_fit[-2:] == [(0, 0), (first, 0)]
    assert all(held == 0 for _, held in watched.at_a_fit)
    # what the dump found aloft is the second fit's, whole
    assert watched.taken[:first] == [t for _, t, _, _ in watched.started[:first]]
    assert built["eager"][3].at_a_fit[-2:] == [(0, 0), (0, 0)]


def test_the_sequential_builder_finds_nothing_of_a_flight_on_the_device(
    tmp_path, monkeypatch
):
    """A machine the fleet path does not train is built after the final
    fits, by programs of its own, and a machine of another fit
    configuration is fitted after the backbone: the backbone's leaves
    are landed before either."""
    # the toy backbone's largest leaf is 12 KiB, the dense machine's 24 bytes
    monkeypatch.setattr(fleet, "DEFER_MIN_MEMBER_LEAF_BYTES", 4 << 10)
    watched = Watched(monkeypatch)
    found = []
    real = ModelBuilder.build

    def sequential(self, *args, **kwargs):
        found.append(watched.on_the_device())
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ModelBuilder, "build", sequential)
    plain = machine("dense", "sk-beside")
    plain.model = {"gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": "sklearn.linear_model.LinearRegression"
    }}
    only_build = {"cv_mode": "build_only"}
    builder, results, spans = build(
        [
            machine("backbone", "bb-before", evaluation=only_build),
            # another fit configuration: a final fit of its own, after
            machine("dense", "dense-after", evaluation=only_build),
            plain,
        ],
        tmp_path,
    )
    assert not builder.build_errors
    assert sorted(made.name for _, made in results) == [
        "bb-before", "dense-after", "sk-beside"
    ]
    assert watched.at_a_fit == [(0, 0), (len(watched.started), 0)]
    assert found == [(len(watched.started), 0)] and watched.started
    (write,) = attributes(spans, "build_part", part="write", phase="dump")
    assert write["bytes_fetched_beside_write"] == 0  # landed before, not beside


# -- the line ---------------------------------------------------------------------


class Leaf:
    def __init__(self, *shape):
        self.shape, self.nbytes = shape, 4 * int(np.prod(shape))


@pytest.mark.parametrize(
    "shapes, deferred",
    [
        ([(1, 16, 2048, 768), (1, 2048)], True),  # a backbone's experts, 96 MiB
        ([(1, 2048, 8192)], True),  # exactly the line
        ([(64, 256, 1024), (64, 1024)], False),  # 64 LSTMs: 64 MiB stacked, 1 MiB a member
        ([(16, 1024, 1024 * 15)], False),  # below it a member, far above it stacked
        ([(160, 20, 10)], False),
        ([(2, 16, 2048, 768)], True),  # two large members in one bucket
    ],
)
def test_the_line_is_drawn_on_one_members_part_of_a_leaf(shapes, deferred):
    assert fleet.defers({"w": [Leaf(*shape) for shape in shapes]}) is deferred


@pytest.mark.parametrize(
    "config, members, deferred",
    [
        ("hourglass-ae-20tag", 160, False),
        ("lstm-ae-50tag-lb60", 16, False),
        ("lstm-ae-50tag-lb60", 64, False),  # leaves of exactly 64 MiB stacked
        ("lfm2-8b-a1b-50tag-lb512", 1, True),
        ("keye-vl2-30b-a3b-50tag-lb8192", 1, True),
        ("laguna-xs2-50tag-lb8192", 1, True),  # leaves of exactly 64 MiB a member
        ("smallthinker-21b-a3b-50tag-lb8192", 1, True),
        ("kanana-2-30b-a3b-50tag-lb8192", 1, True),
    ],
)
def test_the_benchmarks_configurations_fall_where_they_should(config, members, deferred):
    """The stacked parameters of a job's final fit, from shapes alone."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "benchmarks", "chip", "configs", config + ".json"
    )
    with open(path) as f:
        document = json.load(f)
    estimator = serializer.from_definition(document["estimator"])
    _, factory = split_fit_kwargs(
        dict(estimator.kwargs, n_features=document["tags"], n_features_out=document["tags"])
    )
    spec = estimator._build_spec(factory)
    one = jax.eval_shape(lambda key: fleet.init_fn_for(spec)(key, spec), jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct((members,) + leaf.shape, leaf.dtype), one
    )
    sizes = [
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(stacked)
    ]
    stand_ins = [Leaf(*leaf.shape) for leaf in jax.tree_util.tree_leaves(stacked)]
    assert [leaf.nbytes for leaf in stand_ins] == sizes  # float32 throughout
    assert fleet.defers(stand_ins) is deferred
    if config.startswith("lstm") and members == 64:
        assert max(sizes) == fleet._COALESCE_MAX_LEAF_BYTES


def test_several_processes_wait_together(monkeypatch):
    params = {"w": Leaf(1, 16, 2048, 768)}
    assert fleet.defers(params)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert not fleet.defers(params)


@pytest.mark.parametrize("kind", ["dense", "windowed"])
def test_a_small_members_fleet_defers_nothing(kind, tmp_path):
    """The line where it ships: three dense or three LSTM machines."""
    names = [f"{kind}-{i}" for i in range(3)]
    builder, results, spans = build(
        [machine(kind, n, evaluation={"cv_mode": "build_only"}) for n in names], tmp_path
    )
    assert not builder.build_errors and len(results) == 3
    (collect,) = attributes(spans, "build_part", part="collect", phase="final_fit")
    assert collect["bytes_deferred"] == 0 < collect["bytes"]
    (write,) = attributes(spans, "build_part", part="write", phase="dump")
    assert write["bytes_fetched_beside_write"] == 0
    assert status_parts(tmp_path, "final_fit")["collect"]["bytes_deferred"] == 0
    assert "collect" not in status_parts(tmp_path, "dump")
    assert "on their way" not in render_status(
        json.load(open(os.path.join(str(tmp_path), BUILD_STATUS_FILE)))
    )


# -- without a dump, and when a leaf does not land --------------------------------


def test_without_an_output_dir_the_leaves_land_before_build_returns(monkeypatch):
    monkeypatch.setattr(fleet, "DEFER_MIN_MEMBER_LEAF_BYTES", 1)
    watched = Watched(monkeypatch)
    builder = FleetBuilder([machine("backbone", "bb-none")])
    ((model, _),) = builder.build()
    assert watched.started and len(watched.taken) == len(watched.started)
    for leaf in jax.tree_util.tree_leaves(estimator_of(model).params_):
        assert type(leaf) is np.ndarray
    assert "finish" in builder.phase_seconds and not builder.build_errors


def lost_and_kept(tmp_path, monkeypatch, error):
    """A build of a toy backbone whose transfers fail with ``error``
    when a leaf is taken, after ``collect`` returned, and of a dense
    machine beside it, which is never in flight."""
    # the toy backbone's largest leaf is 12 KiB, the dense machine's 24 bytes
    monkeypatch.setattr(fleet, "DEFER_MIN_MEMBER_LEAF_BYTES", 4 << 10)

    def link_down(transfer):
        raise error

    monkeypatch.setattr(in_flight._Transfer, "host", link_down)
    only_build = {"cv_mode": "build_only"}
    builder, results, spans = build(
        [
            machine("backbone", "bb-lost", evaluation=only_build),
            machine("dense", "dense-kept", evaluation=only_build),
        ],
        tmp_path,
    )
    collects = attributes(spans, "build_part", part="collect", phase="final_fit")
    # the backbone's, the wait for it before the dense fit, the dense fit's
    assert sorted(c["bytes_deferred"] > 0 for c in collects) == [False, False, True]
    assert staging_dirs(str(tmp_path)) == []
    assert serializer.load(str(tmp_path / "dense-kept")) is not None
    return builder, results, BuildJournal.load(str(tmp_path)).machines()


@pytest.mark.parametrize(
    "error",
    [ValueError("no such leaf"), InjectedDeviceError("the transfer failed")],
    ids=["host", "device"],
)
def test_a_leaf_that_does_not_land_fails_its_machine_alone(tmp_path, monkeypatch, error):
    """The dump raises what the final fit's ``collect`` would have
    raised, whether the device's error or another: nothing of the
    machine is left on disk or journaled ``built``, nothing is built a
    second time, and the machine beside it is built."""
    monkeypatch.setattr(
        ModelBuilder, "build", lambda *args, **kwargs: pytest.fail("rebuilt")
    )
    builder, results, state = lost_and_kept(tmp_path, monkeypatch, error)
    assert [made.name for _, made in results] == ["dense-kept"]
    assert list(builder.build_errors) == ["bb-lost"] and not builder.degraded
    assert builder.build_errors["bb-lost"] is error
    assert not os.path.exists(tmp_path / "bb-lost")
    assert state["bb-lost"]["status"] == "failed"
    assert str(error) in state["bb-lost"]["error"]
    assert state["dense-kept"]["status"] == "built"
