"""A leaf on its way (models/in_flight.py) is to a pickler the array it
becomes: the same opcodes, one memo slot, so ``model.pkl`` and its md5
do not say which schedule brought the parameters to the host."""

import copy
import hashlib
import pickle
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu import serializer
from gordo_tpu.models import JaxAutoEncoder, in_flight
from gordo_tpu.models.in_flight import Flight, LeafInFlight, for_pickling, landed


def stacked(members=2):
    """Two members' stacked parameters on the device, as a fit program
    returns them: nested, keys out of order, two dtypes."""
    rng = np.random.RandomState(7)

    def leaf(*shape, dtype=np.float32):
        return jnp.asarray((rng.rand(members, *shape) * 100).astype(dtype))

    return {
        "layer_1": {"w": leaf(64, 48), "b": leaf(48)},
        "layer_0": {"w": leaf(3, 64), "steps": leaf(dtype=np.int32)},
        "head": [leaf(48, 3), leaf(3)],
    }


def eager(params, row, own_fetch_bytes):
    """What the eager schedule hands member ``row``: a view of the
    runtime's read-only array for a leaf fetched on its own, of a copy
    for a coalesced one (``fleet.fetch_to_host``)."""

    def fetched(leaf):
        host = np.asarray(leaf)
        return host if leaf.nbytes >= own_fetch_bytes else host.copy()

    return jax.tree_util.tree_map(lambda leaf: np.asarray(fetched(leaf)[row]), params)


def in_flight_params(params, row, own_fetch_bytes, flight=None):
    flight = flight or Flight()
    transfers = jax.tree_util.tree_map(
        lambda leaf: flight.start(leaf, writable=leaf.nbytes < own_fetch_bytes), params
    )
    return flight, jax.tree_util.tree_map(lambda t: LeafInFlight(t, row), transfers)


def estimator(params):
    model = JaxAutoEncoder(kind="feedforward_hourglass")
    model.params_ = params
    return model


@pytest.fixture(params=["row_major", "device_order"])
def layout(request, monkeypatch):
    """How the runtime lays a fetched leaf out on the host: row-major,
    as the CPU backend does, or as the device had it, which on a TPU is
    Fortran order for some leaves (an array that owns its buffer,
    read-only). A coalesced leaf is a row-major copy either way."""
    if request.param == "device_order":
        real = np.asarray

        def as_the_device_had_it(value, *args, **kwargs):
            host = real(value, *args, **kwargs)
            if isinstance(value, jax.Array) and host.ndim > 1:
                host = np.array(host, order="F")
                host.flags.writeable = False
            return host

        monkeypatch.setattr(in_flight.np, "asarray", as_the_device_had_it)
    return request.param


@pytest.mark.parametrize("own_fetch_bytes", [1, 20_000, 1 << 40])
@pytest.mark.parametrize("row", [0, 1])
def test_dump_writes_the_bytes_of_the_arrays_the_leaves_become(
    tmp_path, row, own_fetch_bytes, layout
):
    params = stacked()
    serializer.dump(estimator(eager(params, row, own_fetch_bytes)), str(tmp_path / "eager"))
    flight, waiting = in_flight_params(params, row, own_fetch_bytes)
    assert flight.bytes_landed == 0
    written = serializer.dump(estimator(waiting), str(tmp_path / "deferred"))
    with open(tmp_path / "eager" / "model.pkl", "rb") as f:
        expected = f.read()
    with open(tmp_path / "deferred" / "model.pkl", "rb") as f:
        assert f.read() == expected
    assert written.bytes == len(expected)
    checksum = serializer.load_info(str(tmp_path / "deferred"))["checksum"]
    assert checksum == hashlib.md5(expected).hexdigest()
    assert checksum == serializer.load_info(str(tmp_path / "eager"))["checksum"]
    # every stacked leaf was waited for once, whole
    assert flight.bytes_landed == flight.bytes_started == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )
    assert flight.wait_seconds >= 0.0
    restored = serializer.load(str(tmp_path / "deferred"))
    for leaf, other in zip(
        jax.tree_util.tree_leaves(restored.params_),
        jax.tree_util.tree_leaves(eager(params, row, own_fetch_bytes)),
    ):
        assert type(leaf) is np.ndarray and leaf.dtype == other.dtype
        np.testing.assert_array_equal(leaf, other)


@pytest.mark.parametrize("protocol", range(2, pickle.HIGHEST_PROTOCOL + 1))
@pytest.mark.parametrize("writable", [False, True])
def test_a_leafs_pickle_is_its_arrays_at_every_protocol(protocol, writable):
    device = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4)
    leaf = LeafInFlight(Flight().start(device, writable=writable), 1)
    array = leaf.take()
    assert type(array) is np.ndarray and array.flags.writeable is writable
    # twice in one pickle: the second is a memo reference either way
    assert pickle.dumps([leaf, leaf], protocol) == pickle.dumps([array, array], protocol)
    np.testing.assert_array_equal(pickle.loads(pickle.dumps(leaf, protocol)), array)


def test_a_copy_of_a_leaf_and_an_array_of_it_are_plain_arrays():
    device = jnp.arange(12, dtype=jnp.float32).reshape(2, 6)
    leaf = LeafInFlight(Flight().start(device, writable=True), 0)
    for made in (copy.deepcopy(leaf), copy.copy(leaf), np.asarray(leaf)):
        assert type(made) is np.ndarray
        np.testing.assert_array_equal(made, np.arange(6, dtype=np.float32))
    assert np.asarray(leaf, dtype=np.float64).dtype == np.float64
    assert jax.device_get({"w": leaf})["w"].shape == (6,)


def test_for_pickling_leaves_what_is_on_its_way_and_fetches_the_rest():
    params = stacked()
    _, waiting = in_flight_params(params, 0, 1)
    mixed = dict(waiting, on_device=jnp.ones((2, 2)), on_host=np.zeros(3), number=1.5)
    state = for_pickling(mixed)
    assert list(state) == sorted(mixed)
    assert type(state["on_device"]) is type(state["on_host"]) is np.ndarray
    assert state["number"] == 1.5
    assert state["layer_1"]["w"] is waiting["layer_1"]["w"]
    settled = landed(state)
    for leaf in jax.tree_util.tree_leaves(settled):
        assert type(leaf) is np.ndarray
    np.testing.assert_array_equal(settled["layer_1"]["w"], np.asarray(params["layer_1"]["w"])[0])
    # an estimator's state is this, and leaves the estimator as it was
    model = estimator(waiting)
    assert model.__getstate__()["params_"]["head"][0] is waiting["head"][0]
    assert model.params_ is waiting


def test_the_devices_copy_goes_when_the_hosts_is_there_and_a_wait_is_counted_once():
    flight = Flight()
    transfer = flight.start(jnp.ones((2, 1000), jnp.float32), writable=False)
    assert flight.bytes_started == 8000 and transfer._device is not None
    first = transfer.host()
    assert transfer._device is None and transfer.host() is first
    assert flight.bytes_landed == 8000


def test_two_members_picklers_wait_for_a_shared_leaf_once(monkeypatch):
    """One bucket's members refer to one stacked leaf: whichever thread
    comes first fetches it, and the other finds it there."""
    flight = Flight()
    transfer = flight.start(jnp.ones((2, 1000), jnp.float32), writable=True)
    fetches, gate = [], threading.Event()
    real = np.asarray

    def slow(value, *args, **kwargs):
        if isinstance(value, jax.Array):
            fetches.append(1)
            gate.wait(5)
        return real(value, *args, **kwargs)

    monkeypatch.setattr(in_flight.np, "asarray", slow)
    taken = []
    threads = [
        threading.Thread(target=lambda row=row: taken.append(LeafInFlight(transfer, row).take()))
        for row in (0, 1)
    ]
    for thread in threads:
        thread.start()
    gate.set()
    for thread in threads:
        thread.join(30)
    assert not any(thread.is_alive() for thread in threads)
    assert len(fetches) == 1 and len(taken) == 2
    assert flight.bytes_landed == 8000
    assert taken[0].base is taken[1].base is transfer.host()


def test_many_takers_of_shared_leaves_count_every_byte_once():
    """More threads than cores over twenty shared leaves, switching
    often: no wait is counted twice and none is lost."""
    flight = Flight()
    transfers = [
        flight.start(jnp.full((4, 250), k, jnp.float32), writable=k % 2 == 0)
        for k in range(20)
    ]
    started, failures = threading.Event(), []

    def take_all(row):
        started.wait(5)
        try:
            for k, transfer in enumerate(transfers):
                taken = LeafInFlight(transfer, row % 4).take()
                assert taken.shape == (250,) and (taken == k).all()
        except BaseException as exc:  # noqa: BLE001 - reported by the assert below
            failures.append(exc)

    threads = [threading.Thread(target=take_all, args=(row,)) for row in range(32)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for thread in threads:
            thread.start()
        started.set()
        for thread in threads:
            thread.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not failures and not any(thread.is_alive() for thread in threads)
    assert flight.bytes_landed == flight.bytes_started == 20 * 4 * 250 * 4
    assert all(transfer._device is None for transfer in transfers)


def test_a_transfer_that_fails_raises_to_its_taker_and_keeps_nothing(monkeypatch):
    flight = Flight()
    transfer = flight.start(jnp.ones((1, 10), jnp.float32), writable=False)
    real = np.asarray
    state = {"fail": True}

    def failing(value, *args, **kwargs):
        if state["fail"] and isinstance(value, jax.Array):
            raise RuntimeError("DATA_LOSS: the transfer failed")
        return real(value, *args, **kwargs)

    monkeypatch.setattr(in_flight.np, "asarray", failing)
    leaf = LeafInFlight(transfer, 0)
    with pytest.raises(RuntimeError, match="DATA_LOSS"):
        pickle.dumps(leaf, 5)
    assert flight.bytes_landed == 0 and transfer._device is not None
    state["fail"] = False
    assert pickle.loads(pickle.dumps(leaf, 5)).shape == (10,)
