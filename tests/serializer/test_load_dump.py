import os

import numpy as np
import pytest
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import MinMaxScaler

from gordo_tpu import serializer
from gordo_tpu.models import JaxAutoEncoder


def test_dumps_loads_bytes():
    scaler = MinMaxScaler(feature_range=(0, 2))
    restored = serializer.loads(serializer.dumps(scaler))
    assert restored.feature_range == (0, 2)


def test_dump_load_directory(tmp_path):
    X = np.random.RandomState(0).rand(64, 3).astype(np.float32)
    pipe = Pipeline(
        [
            ("scale", MinMaxScaler()),
            ("model", JaxAutoEncoder(kind="feedforward_hourglass", epochs=1)),
        ]
    )
    pipe.fit(X, X)
    expected = pipe.predict(X)

    serializer.dump(pipe, tmp_path, metadata={"machine": "m1"}, info={"extra": 1})
    restored = serializer.load(tmp_path)
    np.testing.assert_allclose(restored.predict(X), expected, rtol=1e-5)

    metadata = serializer.load_metadata(tmp_path)
    assert metadata["machine"] == "m1"
    info = serializer.load_info(tmp_path)
    assert "checksum" in info and info["extra"] == 1


def test_load_metadata_parent_fallback(tmp_path):
    sub = tmp_path / "sub"
    sub.mkdir()
    serializer.dump(MinMaxScaler(), tmp_path, metadata={"at": "parent"})
    assert serializer.load_metadata(str(sub))["at"] == "parent"


def test_load_metadata_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        serializer.load_metadata(str(tmp_path / "nothing"))


def test_dump_atomic_replaces_prior_artifact(tmp_path):
    dest = tmp_path / "model-dir"
    serializer.dump_atomic(MinMaxScaler(feature_range=(0, 2)), str(dest))
    serializer.dump_atomic(MinMaxScaler(feature_range=(0, 5)), str(dest))
    assert serializer.load(str(dest)).feature_range == (0, 5)
    # no staging dirs left behind
    assert [e for e in tmp_path.iterdir() if e.name.startswith(".")] == []


def test_dump_atomic_preserves_unrelated_files_in_mixed_dir(tmp_path):
    """The legacy dump merged into an existing dir; dump_atomic must never
    rmtree a dest holding other content (`gordo build config.yaml .` would
    otherwise delete the user's working directory)."""
    dest = tmp_path / "workdir"
    dest.mkdir()
    (dest / "notes.txt").write_text("keep me")
    serializer.dump_atomic(MinMaxScaler(), str(dest), metadata={"m": 1})
    assert (dest / "notes.txt").read_text() == "keep me"
    assert serializer.load_metadata(str(dest))["m"] == 1
    assert isinstance(serializer.load(str(dest)), MinMaxScaler)
    assert [e for e in tmp_path.iterdir() if e.name.startswith(".")] == []


def test_dump_atomic_dir_mode_honors_umask(tmp_path):
    """mkdtemp's private 0700 must not leak onto artifact dirs — the model
    server often runs as a different UID on the shared volume."""
    import os
    import stat

    dest = tmp_path / "served-model"
    serializer.dump_atomic(MinMaxScaler(), str(dest))
    umask = os.umask(0)
    os.umask(umask)
    expected = 0o777 & ~umask
    assert stat.S_IMODE(os.stat(dest).st_mode) == expected


# ---------------------------------------------------------------------
# model.pkl is hashed as it is written: one pass over its bytes
# ---------------------------------------------------------------------

LARGE = serializer.serializer.HASH_BESIDE_WRITE_MIN_BYTES


def _small_sklearn():
    return MinMaxScaler(feature_range=(0, 2)).fit(np.arange(12.0).reshape(4, 3))


def _leaves_under_the_constant():
    rng = np.random.RandomState(1)
    return {f"w{i}": rng.rand(64, 48).astype(np.float32) for i in range(5)}


def _two_large_leaves():
    """Two float32 leaves of 8 MB, the first read-only as the fetched
    copy of a ``jax.Array`` is, beside a small one and a scalar."""
    rng = np.random.RandomState(2)
    big = [rng.rand(1024, 2048).astype(np.float32) for _ in range(2)]
    big[0].setflags(write=False)
    assert all(leaf.nbytes >= LARGE for leaf in big)
    return {"frozen": big[0], "plain": big[1], "bias": np.ones(7, np.float16), "n": 3}


ARTIFACTS = {
    "sklearn": _small_sklearn,
    "small-leaves": _leaves_under_the_constant,
    "large-leaves": _two_large_leaves,
}


def _md5_of(file_path) -> str:
    import hashlib

    with open(file_path, "rb") as f:
        return hashlib.md5(f.read()).hexdigest()


def _leaves(obj):
    if isinstance(obj, dict):
        return obj
    return {k: v for k, v in vars(obj).items() if isinstance(v, np.ndarray)}


@pytest.mark.parametrize("make", ARTIFACTS.values(), ids=list(ARTIFACTS))
def test_checksum_is_the_md5_of_the_file_and_the_journal_accepts_it(make, tmp_path):
    from gordo_tpu.parallel import journal

    dest = tmp_path / "m"
    serializer.dump_atomic(make(), str(dest), metadata={"name": "m"})
    assert serializer.load_info(str(dest))["checksum"] == _md5_of(dest / "model.pkl")
    assert journal.artifact_complete(str(dest))


@pytest.mark.parametrize("make", ARTIFACTS.values(), ids=list(ARTIFACTS))
def test_round_trip_is_bit_for_bit_and_keeps_the_writeable_flag(make, tmp_path):
    """Values, dtypes and shapes are what they were. The flag is the
    dumped leaf's own: in-band protocol 5 restores a read-only leaf (a
    fetched ``jax.Array``) read-only where protocol 4 handed back a
    writable copy; nothing in the package writes into loaded parameters."""
    obj = make()
    serializer.dump(obj, str(tmp_path))
    restored = serializer.load(str(tmp_path))
    assert type(restored) is type(obj)
    before, after = _leaves(obj), _leaves(restored)
    assert list(before) == list(after) and before
    for name, leaf in before.items():
        if not isinstance(leaf, np.ndarray):
            assert after[name] == leaf
            continue
        assert after[name].dtype == leaf.dtype and after[name].shape == leaf.shape
        assert after[name].tobytes() == leaf.tobytes()
        assert after[name].flags.writeable == leaf.flags.writeable, name


def test_a_loaded_estimator_has_its_parameters_as_they_were_fetched(tmp_path):
    X = np.random.RandomState(0).rand(64, 3).astype(np.float32)
    model = JaxAutoEncoder(kind="feedforward_hourglass", epochs=1).fit(X, X)
    import jax

    fetched = jax.tree_util.tree_leaves(model.__getstate__()["params_"])
    serializer.dump(model, str(tmp_path))
    restored = serializer.load(str(tmp_path))
    loaded = jax.tree_util.tree_leaves(restored.params_)
    assert len(loaded) == len(fetched) > 0
    for a, b in zip(fetched, loaded):
        assert isinstance(b, np.ndarray) and b.dtype == a.dtype and b.shape == a.shape
        assert b.tobytes() == a.tobytes()
        assert b.flags.writeable == a.flags.writeable
    np.testing.assert_array_equal(restored.predict(X), model.predict(X))


@pytest.mark.parametrize("make", ARTIFACTS.values(), ids=list(ARTIFACTS))
def test_an_earlier_builds_protocol_4_artifact_loads_and_verifies(make, tmp_path):
    import json
    import pickle

    from gordo_tpu.parallel import journal

    obj = make()
    with open(tmp_path / "model.pkl", "wb") as f:
        pickle.dump(obj, f, protocol=4)
    (tmp_path / "metadata.json").write_text("{}")
    (tmp_path / "info.json").write_text(
        json.dumps({"checksum": _md5_of(tmp_path / "model.pkl")})
    )
    assert journal.artifact_complete(str(tmp_path))
    before, after = _leaves(obj), _leaves(serializer.load(str(tmp_path)))
    for name, leaf in before.items():
        assert np.array_equal(after[name], leaf)


@pytest.mark.parametrize("make", ARTIFACTS.values(), ids=list(ARTIFACTS))
def test_model_pkl_is_the_protocol_5_pickle_and_nothing_else(make, tmp_path):
    """The writer adds and drops nothing, and only a buffer of the
    constant's size or more takes the helper thread."""
    import pickle

    obj = make()
    written = serializer.dump(obj, str(tmp_path))
    expected = pickle.dumps(obj, protocol=5)
    assert (tmp_path / "model.pkl").read_bytes() == expected
    assert written.bytes == len(expected)
    large = sum(
        leaf.nbytes
        for leaf in _leaves(obj).values()
        if isinstance(leaf, np.ndarray) and leaf.nbytes >= LARGE
    )
    assert written.bytes_hashed_beside_write == large
    assert (large > 0) == (make is _two_large_leaves)


def test_a_dense_members_artifact_starts_no_helper_thread(tmp_path, monkeypatch):
    import threading

    X = np.random.RandomState(0).rand(64, 3).astype(np.float32)
    pipe = Pipeline(
        [("scale", MinMaxScaler()), ("model", JaxAutoEncoder(kind="feedforward_hourglass", epochs=1))]
    ).fit(X, X)
    started = []
    monkeypatch.setattr(
        threading.Thread, "start", lambda self: started.append(self.name)
    )
    written = serializer.dump_atomic(pipe, str(tmp_path / "dense"))
    assert written.bytes == (tmp_path / "dense" / "model.pkl").stat().st_size > 0
    assert written.bytes_hashed_beside_write == 0 and started == []


class _FailsAfter:
    """A binary file whose ``write`` raises once ``limit`` bytes went in."""

    def __init__(self, file, limit):
        self.file, self.left = file, limit

    def write(self, data):
        size = memoryview(data).nbytes
        if size > self.left:
            self.file.write(memoryview(data).cast("B")[: self.left])
            self.left = 0
            raise OSError(28, "No space left on device")
        self.left -= size
        return self.file.write(data)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.file.close()


@pytest.mark.parametrize(
    "limit", [100, 3 * LARGE, 12 * LARGE], ids=["in-a-frame", "in-leaf-1", "in-leaf-2"]
)
def test_a_write_that_fails_half_way_leaves_nothing_behind(tmp_path, monkeypatch, limit):
    import builtins
    import threading

    def failing_open(file, mode="r", *args, **kwargs):
        opened = builtins.open(file, mode, *args, **kwargs)
        if str(file).endswith(serializer.MODEL_FILE) and "w" in mode:
            return _FailsAfter(opened, limit)
        return opened

    monkeypatch.setattr(serializer.serializer, "open", failing_open, raising=False)
    threads = threading.active_count()
    dest = tmp_path / "never"
    with pytest.raises(OSError, match="No space left"):
        serializer.dump_atomic(_two_large_leaves(), str(dest), metadata={})
    assert not dest.exists() and list(tmp_path.iterdir()) == []
    assert threading.active_count() == threads


def test_dump_opens_model_pkl_once_and_never_for_reading(tmp_path, monkeypatch):
    import builtins

    opened = []

    def recording_open(file, mode="r", *args, **kwargs):
        opened.append((os.path.basename(str(file)), mode))
        return builtins.open(file, mode, *args, **kwargs)

    monkeypatch.setattr(serializer.serializer, "open", recording_open, raising=False)
    serializer.dump(_two_large_leaves(), str(tmp_path), metadata={})
    assert [o for o in opened if o[0] == serializer.MODEL_FILE] == [
        (serializer.MODEL_FILE, "wb")
    ]
