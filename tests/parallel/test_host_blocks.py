"""The staging pool (``parallel/host_blocks.py``): a leased block holds
what the last user left, so every site writes what ``np.zeros`` used to
give it, and a block goes back only when the program that read it has
answered. On the CPU backend a device array may alias the host buffer it
was put from, which is the case these tests run."""

import concurrent.futures
import hashlib
import json
import sys
import types

import jax
import numpy as np
import pandas as pd
import pytest
import yaml
from click.testing import CliRunner
from sklearn.preprocessing import MinMaxScaler

from gordo_tpu.cli import gordo_tpu_cli
from gordo_tpu.models.anomaly.diff import DiffBasedAnomalyDetector
from gordo_tpu.models.factories import feedforward_symmetric, lstm_model
from gordo_tpu.models.training import FitConfig
from gordo_tpu.ops.windows import window_targets
from gordo_tpu.parallel import (
    FleetBuilder,
    FleetMember,
    FleetTrainer,
    WindowedFleetMember,
    host_blocks,
)
from gordo_tpu.parallel.fleet_build import _Plan
from gordo_tpu.telemetry.progress import load_status

TAGS = 3
SPEC = feedforward_symmetric(TAGS, TAGS, dims=(4,), funcs=("tanh",))
LOOKBACK = 4
LSTM_SPEC = lstm_model(
    TAGS, lookback_window=LOOKBACK, encoding_dim=(4,), encoding_func=("tanh",),
    decoding_dim=(4,), decoding_func=("tanh",),
)
CONFIG = FitConfig(epochs=2, batch_size=8, shuffle=True, validation_split=0.25)


@pytest.fixture(autouse=True)
def empty_pool():
    host_blocks.clear()
    yield
    host_blocks.clear()


def dense_bucket(count, rows, seed):
    rng = np.random.RandomState(seed)
    members = []
    for i in range(count):
        X = rng.rand(rows - i, TAGS).astype(np.float32) + 1.0  # no zero anywhere
        members.append(
            FleetMember(name=f"d{seed}-{i}", spec=SPEC, X=X, y=X * 2.0, seed=seed + i)
        )
    return members


def windowed_bucket(count, rows, seed):
    rng = np.random.RandomState(seed)
    members = []
    for i in range(count):
        series = rng.rand(rows - i, TAGS).astype(np.float32) + 1.0
        members.append(
            WindowedFleetMember(
                name=f"w{seed}-{i}", spec=LSTM_SPEC, series=series,
                targets=window_targets(series, LOOKBACK, 0), seed=seed + i,
            )
        )
    return members


def dirty_the_pool(nbytes=1 << 20, buffers=8):
    """``buffers`` free buffers of ``nbytes`` in which no byte is zero,
    and no other."""
    host_blocks.clear()
    with host_blocks.lease() as blocks:
        for _ in range(buffers):
            blocks.zeros((nbytes,), np.uint8).fill(0xA5)
    assert host_blocks.free_nbytes() == nbytes * buffers


# -- the pool -------------------------------------------------------------------


def test_a_lease_after_a_return_shares_memory_and_a_larger_one_does_not():
    with host_blocks.lease() as blocks:
        first = blocks.zeros((64, 32))
        assert blocks.bytes_reused == 0
    with host_blocks.lease() as blocks:
        again = blocks.zeros((32, 16), np.int32)  # smaller, another dtype
        assert np.shares_memory(again, first)
        assert blocks.bytes_reused == again.nbytes
        larger = blocks.zeros((64, 33))
        assert not np.shares_memory(larger, first)
        assert blocks.bytes_reused == again.nbytes


def test_a_block_is_not_leased_twice_and_the_best_fit_is_taken():
    with host_blocks.lease() as blocks:
        small, large = blocks.zeros((10,)), blocks.zeros((1000,))
    with host_blocks.lease() as blocks:
        one = blocks.zeros((8,))
        two = blocks.zeros((8,))
        assert np.shares_memory(one, small) and np.shares_memory(two, large)
        three = blocks.zeros((8,))
        assert not np.shares_memory(three, small) and not np.shares_memory(three, large)


def test_a_lease_that_ends_in_an_exception_gives_nothing_back():
    with pytest.raises(RuntimeError):
        with host_blocks.lease() as blocks:
            blocks.zeros((64,))
            raise RuntimeError("the program failed: a transfer may be in flight")
    assert host_blocks.free_nbytes() == 0


def test_zeros_and_stacked_hold_what_np_zeros_and_the_fill_gave():
    rows = [np.full((n, 2), n, np.float32) for n in (5, 3, 0)]
    expected = np.zeros((5, 6, 2), np.float32)
    for i, a in enumerate(rows):
        expected[i, : len(a)] = a
    for dirty in (False, True):
        if dirty:
            dirty_the_pool()
        with host_blocks.lease() as blocks:
            stacked = blocks.stacked((5, 6, 2), iter(rows))
            zeros = blocks.zeros((7, 3), np.int32)
            assert blocks.bytes_reused == (stacked.nbytes + zeros.nbytes) * dirty
            np.testing.assert_array_equal(stacked, expected)
            assert stacked.dtype == np.float32 and zeros.dtype == np.int32
            assert not zeros.any() and zeros.shape == (7, 3)
            assert blocks.zeros((4, 0)).shape == (4, 0)  # nothing to lease


def test_trim_drops_what_no_lease_took_since_the_trim_before():
    with host_blocks.lease() as blocks:
        blocks.zeros((100,)), blocks.zeros((1000,))
    host_blocks.trim()  # the end of the build that made them: both stay
    assert host_blocks.free_nbytes() == 4400
    with host_blocks.lease() as blocks:
        blocks.zeros((50,))
        host_blocks.trim()  # a build that ends while a lease is out
    assert host_blocks.free_nbytes() == 400
    host_blocks.trim()  # the small one came back after it: it stays
    assert host_blocks.free_nbytes() == 400
    host_blocks.trim()  # and a build that took none
    assert host_blocks.free_nbytes() == 0


def test_a_buffer_unused_during_a_build_is_gone_after_it():
    with host_blocks.lease() as blocks:
        blocks.zeros((100,))
    FleetBuilder([]).build()  # given back since the build before: it stays
    assert host_blocks.free_nbytes() == 400
    FleetBuilder([]).build()
    assert host_blocks.free_nbytes() == 0


def test_concurrent_leases_never_share_a_buffer():
    """More threads than cores lease, fill with their own number, check
    and give back: a buffer handed to two leases at once would be read
    with the other's number in it."""

    def work(number):
        for _ in range(50):
            with host_blocks.lease() as blocks:
                block = blocks.zeros((257,), np.int32)
                assert not block.any()
                block.fill(number)
                stacked = blocks.stacked((3, 50), [np.full(40, number, np.float32)])
                assert (block == number).all()
                assert (stacked[0, :40] == number).all() and not stacked[0, 40:].any()
                assert not stacked[1:].any()
        return number

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(32) as pool:
            done = list(pool.map(work, range(1, 33), timeout=120))
    finally:
        sys.setswitchinterval(interval)
    assert done == list(range(1, 33))
    # what 32 threads held at most, and nothing leaked beside the pool
    assert 0 < host_blocks.free_nbytes() <= 32 * (257 * 4 + 3 * 50 * 4)


# -- the trainer's blocks ---------------------------------------------------------


def _host(arrays):
    return [None if a is None else np.array(a) for a in arrays]


def _assert_same_arrays(left, right):
    assert len(left) == len(right)
    for a, b in zip(left, right):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("m_padded", [None, 8])
def test_stack_bucket_on_a_dirty_pool_hands_over_the_same_bits(m_padded):
    """Rows past a member's end, dummy members and both weight planes: a
    bucket of more and longer members was in the blocks before."""
    trainer = FleetTrainer()
    bucket = dense_bucket(3, 20, seed=1)

    def stack():
        with host_blocks.lease() as blocks:
            arrays, slots = trainer._stack_bucket(
                SPEC, 32, bucket, CONFIG, blocks, m_padded=m_padded
            )
            return _host(arrays), slots, blocks.bytes_reused

    clean, clean_slots, reused = stack()
    assert reused == 0
    host_blocks.clear()
    with host_blocks.lease() as blocks:
        trainer._stack_bucket(SPEC, 64, dense_bucket(11, 60, seed=2), CONFIG, blocks)
    dirty, dirty_slots, reused = stack()
    assert clean_slots == dirty_slots > 0
    _assert_same_arrays(clean, dirty)
    X, y, wtr, wval = clean[0], clean[1], clean[2], clean[5]
    assert reused == X.nbytes + y.nbytes + wtr.nbytes + wval.nbytes
    # and they are what the members say: rows, then zeros; dummies all zero
    for i, member in enumerate(bucket):
        np.testing.assert_array_equal(X[i, : member.n], member.X)
        assert not X[i, member.n :].any() and not y[i, member.n :].any()
        assert wtr[i].sum() + wval[i].sum() == member.n
    assert not X[len(bucket) :].any() and not wtr[len(bucket) :].any()
    assert not wval[len(bucket) :].any()


def test_stack_windowed_bucket_on_a_dirty_pool_hands_over_the_same_bits():
    trainer = FleetTrainer()
    bucket = windowed_bucket(3, 20, seed=3)

    def stack():
        with host_blocks.lease() as blocks:
            arrays, slots = trainer._stack_windowed_bucket(
                LSTM_SPEC, 24, LOOKBACK - 1, bucket, CONFIG, blocks
            )
            return _host(arrays), slots, blocks.bytes_reused

    clean, clean_slots, reused = stack()
    assert reused == 0
    host_blocks.clear()
    with host_blocks.lease() as blocks:
        trainer._stack_windowed_bucket(
            LSTM_SPEC, 64, LOOKBACK - 1, windowed_bucket(11, 60, seed=4), CONFIG, blocks
        )
    dirty, dirty_slots, reused = stack()
    assert clean_slots == dirty_slots > 0
    _assert_same_arrays(clean, dirty)
    assert reused == sum(a.nbytes for a in clean[:5])


def _results_on_host(results):
    return [
        (
            r.name,
            jax.tree_util.tree_map(np.array, r.params),
            dict(r.history.history),
        )
        for r in results
    ]


def _assert_same_results(left, right):
    assert [name for name, _, _ in left] == [name for name, _, _ in right]
    for (_, params, history), (_, other, other_history) in zip(left, right):
        assert history == other_history
        for a, b in zip(
            jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(other)
        ):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize(
    "make_bucket,rows",
    [(dense_bucket, (40, 24)), (windowed_bucket, (40, 24))],
    ids=["dense", "windowed"],
)
def test_two_buckets_through_one_block_train_as_each_does_alone(make_bucket, rows):
    """The first bucket's results are read after the second has run in
    the first's block: nothing of a result may alias a block given back."""
    trainer = FleetTrainer()
    first, second = make_bucket(5, rows[0], seed=5), make_bucket(3, rows[1], seed=6)
    alone = []
    for bucket in (first, second):
        host_blocks.clear()
        alone.append(_results_on_host(trainer.train(bucket, CONFIG)))
    host_blocks.clear()
    first_results = trainer.train(first, CONFIG)
    held = host_blocks.free_nbytes()
    assert held > 0
    second_results = trainer.train(second, CONFIG)
    assert host_blocks.free_nbytes() == held  # the second fit in the first's blocks
    _assert_same_results(_results_on_host(first_results), alone[0])
    _assert_same_results(_results_on_host(second_results), alone[1])


def test_fold_models_left_on_the_device_outlive_the_block_they_trained_from():
    """``params_on_device``: the results refer to the program's output
    block on the device, which a later fit in the same host block must
    not move."""
    trainer = FleetTrainer()
    first, second = dense_bucket(4, 40, seed=7), dense_bucket(4, 40, seed=8)
    host_blocks.clear()
    expected = _results_on_host(trainer.train(first, CONFIG))
    host_blocks.clear()
    kept = trainer.train(first, CONFIG, params_on_device=True)
    trainer.train(second, CONFIG)
    block = jax.device_get(trainer.device_params(SPEC, kept))
    for i, (_, params, _) in enumerate(expected):
        for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(jax.tree_util.tree_map(lambda leaf: leaf[i], block)),
        ):
            np.testing.assert_array_equal(a, b)


# -- the builder's blocks ---------------------------------------------------------


def _plan(name, rows, seed, scored_on_device=True):
    rng = np.random.RandomState(seed)
    y = pd.DataFrame(rng.rand(rows, TAGS) + 1.0, columns=["a", "b", "c"])
    evaluation = {} if scored_on_device else {"metrics": [lambda *a, **k: 0.0]}
    detector = DiffBasedAnomalyDetector(
        base_estimator=None, scaler=MinMaxScaler(), window=None
    )
    plan = _Plan(
        machine=types.SimpleNamespace(name=name, evaluation=evaluation),
        dataset=None, model_obj=None, detector=detector, pipeline=None,
        estimator=None, y=y, y_arr=y.to_numpy(),
    )
    plan.windows = plan.X_arr = (rng.rand(rows, TAGS) + 1.0).astype(np.float32)
    return plan


def test_fold_scoring_on_a_dirty_pool_holds_the_same_targets():
    """``y_true``: a member's rows past its fold's end and a member the
    host scores (no row at all) are zeros in a block that held none."""
    plans = [_plan("p0", 30, 1), _plan("p1", 30, 2, scored_on_device=False), _plan("p2", 30, 3)]
    group = [(plan, 0) for plan in plans]
    fold_rows = [
        (np.arange(10), np.arange(10, 10 + n), np.arange(10, 10 + n)) for n in (12, 9, 5)
    ]
    builder = FleetBuilder([])
    clean = builder._fold_scoring(group, fold_rows, None, host_blocks.Lease())
    dirty_the_pool()
    with host_blocks.lease() as blocks:
        dirty = builder._fold_scoring(group, fold_rows, None, blocks)
        assert blocks.bytes_reused == dirty.y_true.nbytes
        np.testing.assert_array_equal(clean.y_true, dirty.y_true)
        for name in ("rows", "metric_scaler", "error_scaler"):
            np.testing.assert_array_equal(getattr(clean, name), getattr(dirty, name))
    assert list(clean.rows) == [12, 0, 5]
    np.testing.assert_array_equal(
        clean.y_true[0, :12], plans[0].y_arr[10:22].astype(np.float32)
    )
    assert not clean.y_true[0, 12:].any() and not clean.y_true[1].any()
    assert not clean.y_true[2, 5:].any()


MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "sklearn.pipeline.Pipeline": {
                "steps": [
                    "sklearn.preprocessing.MinMaxScaler",
                    {
                        "gordo_tpu.models.JaxAutoEncoder": {
                            "kind": "feedforward_hourglass",
                            "encoding_layers": 1,
                            "epochs": 2,
                        }
                    },
                ]
            }
        }
    }
}
LSTM_MODEL = {
    "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
        "base_estimator": {
            "sklearn.pipeline.Pipeline": {
                "steps": [
                    "sklearn.preprocessing.MinMaxScaler",
                    {
                        "gordo_tpu.models.JaxLSTMAutoEncoder": {
                            "kind": "lstm_hourglass",
                            "lookback_window": 6,
                            "encoding_layers": 1,
                            "epochs": 1,
                        }
                    },
                ]
            }
        }
    }
}
#: two jobs of unlike machine counts and history lengths, the larger first
JOBS = {
    "large": (["pool-a", "pool-b", "pool-c"], "2020-01-09T00:00:00+00:00"),
    "small": (["pool-d", "pool-e"], "2020-01-04T00:00:00+00:00"),
}


def run_job(root, job, model=MODEL):
    """One ``build-fleet`` command in this process; its directory."""
    names, end = JOBS[job]
    config = root / f"{job}.yaml"
    config.write_text(
        yaml.safe_dump(
            {
                "machines": [
                    {
                        "name": name,
                        "model": model,
                        "dataset": {
                            "type": "RandomDataset",
                            "train_start_date": "2020-01-01T00:00:00+00:00",
                            "train_end_date": end,
                            # the data is seeded by the tag's name
                            "tag_list": [f"{name}-{t}" for t in range(3)],
                        },
                    }
                    for name in names
                ]
            }
        )
    )
    out = root / job
    result = CliRunner().invoke(
        gordo_tpu_cli,
        ["build-fleet", str(config), str(out)],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    return out


def artifacts(out, job):
    """Each machine's ``model.pkl`` md5, fold scores and thresholds."""
    found = {}
    for name in JOBS[job][0]:
        with open(out / name / "model.pkl", "rb") as f:
            digest = hashlib.md5(f.read()).hexdigest()
        with open(out / name / "metadata.json") as f:
            model = json.load(f)["metadata"]["build_metadata"]["model"]
        validation = model["cross_validation"]
        found[name] = (
            digest,
            json.dumps(validation["scores"], sort_keys=True),
            json.dumps(
                [
                    model["model_meta"][key]
                    for key in (
                        "feature-thresholds", "aggregate-threshold",
                        "feature-thresholds-per-fold", "aggregate-thresholds-per-fold",
                    )
                ],
                sort_keys=True,
            ),
        )
    assert len({digest for digest, _, _ in found.values()}) == len(found)
    return found


@pytest.mark.parametrize("model", [MODEL, LSTM_MODEL], ids=["dense", "windowed"])
def test_two_jobs_in_one_process_build_what_they_build_on_an_empty_pool(tmp_path, model):
    (tmp_path / "kept").mkdir()
    (tmp_path / "emptied").mkdir()
    kept = {"large": run_job(tmp_path / "kept", "large", model)}
    held = host_blocks.free_nbytes()
    kept["small"] = run_job(tmp_path / "kept", "small", model)
    assert host_blocks.free_nbytes() == held > 0  # no new buffer, none dropped
    host_blocks.clear()
    emptied = {}
    for job in JOBS:
        emptied[job] = run_job(tmp_path / "emptied", job, model)
        host_blocks.clear()
    for job in JOBS:
        assert artifacts(kept[job], job) == artifacts(emptied[job], job)

    def stack(out, phase):
        return load_status(str(out))["phases"][phase]["parts"]["stack"]

    # the process's first cv_train finds nothing; within the job the later
    # phases take cv_train's buffers, and the next job's cv_train all of it
    first = stack(kept["large"], "cv_train")
    assert first["bytes"] > 0 and first["bytes_reused"] == 0
    for phase in ("final_fit", "cv_predict"):
        measured = stack(kept["large"], phase)
        assert measured["bytes_reused"] == measured["bytes"] > 0
    for phase in ("cv_train", "final_fit", "cv_predict"):
        measured = stack(kept["small"], phase)
        assert measured["bytes_reused"] == measured["bytes"] > 0
    # cv_score's bytes count the scalers and the row counts too, which are
    # small arrays of their own: y_true is the block
    measured = stack(kept["small"], "cv_score")
    assert 0 < measured["bytes_reused"] < measured["bytes"]
    for out in emptied.values():
        assert stack(out, "cv_train")["bytes_reused"] == 0

    shown = CliRunner().invoke(gordo_tpu_cli, ["build-status", str(kept["small"])])
    assert shown.exit_code == 0, shown.output
    # cv_train's own lines: a later phase's stack line can read the same to the digit
    (line,) = [
        line for line in shown.output.split("cv_train")[1].split("cv_")[0].splitlines()
        if line.strip().startswith("stack") and "100% reused" in line
    ]
    shown = CliRunner().invoke(gordo_tpu_cli, ["build-status", str(kept["large"])])
    assert "0% reused" in shown.output.split("cv_train")[1].split("cv_")[0]
