"""
Raw-column device-transfer units: both rungs (per-column dlpack and host
staging) must produce the same values ON THE SERVING DEVICE, the rung is
chosen by inspecting the columns with the reason counted, and a refusal
from the transfer itself propagates — it is never converted into the
host rung.
"""

import numpy as np
import pytest

import jax

from gordo_tpu.ingest import (
    RawColumns,
    ingest_stats,
    reset_ingest_stats,
    to_device,
)

pytestmark = pytest.mark.ingest


@pytest.fixture(autouse=True)
def fresh_stats():
    reset_ingest_stats()
    yield
    reset_ingest_stats()


def _columns(rows=6, width=3, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=rows) for _ in range(width)]


def test_raw_columns_shapes_and_host_matrix():
    cols = _columns()
    raw = RawColumns.from_columns(cols)
    assert (raw.rows, raw.width) == (6, 3)
    host = raw.host_matrix()
    assert host.dtype == np.float32 and host.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(
        host, np.column_stack(cols).astype(np.float32)
    )
    assert raw.host_matrix() is host  # built at most once
    assert raw.nbytes == sum(c.nbytes for c in cols)

    mat = np.column_stack(cols)
    raw_m = RawColumns.from_matrix(mat)
    assert (raw_m.rows, raw_m.width) == (6, 3)
    np.testing.assert_array_equal(raw_m.host_matrix(), host)


def test_dlpack_and_host_rungs_agree():
    cols = _columns()
    want = np.column_stack(cols).astype(np.float32)
    fast = np.asarray(to_device(RawColumns.from_columns(cols), dlpack=True))
    slow = np.asarray(to_device(RawColumns.from_columns(cols), dlpack=False))
    np.testing.assert_array_equal(fast, want)
    np.testing.assert_array_equal(slow, want)
    stats = ingest_stats()
    assert stats["dlpack_transfers"] == 1
    assert stats["host_transfers"] == 1
    assert stats["dlpack_columns"] == 3
    assert stats["fallback_reasons"] == {"disabled": 1}


def test_row_padding_happens_on_both_rungs():
    cols = _columns(rows=5)
    for dlpack in (True, False):
        X = np.asarray(
            to_device(
                RawColumns.from_columns(cols), padded_rows=8, dlpack=dlpack
            )
        )
        assert X.shape == (8, 3)
        np.testing.assert_array_equal(
            X[:5], np.column_stack(cols).astype(np.float32)
        )
        np.testing.assert_array_equal(X[5:], 0.0)


def test_matrix_mode_takes_the_host_rung():
    mat = np.column_stack(_columns())
    X = np.asarray(to_device(RawColumns.from_matrix(mat), dlpack=True))
    np.testing.assert_array_equal(X, mat.astype(np.float32))
    stats = ingest_stats()
    assert stats["host_transfers"] == 1
    assert stats["fallback_reasons"] == {"no_columns": 1}


def test_both_rungs_land_on_the_serving_device():
    """The contract the chip smoke asserts on a TPU: whatever the rung,
    the staged batch sits on ``jax.devices()[0]`` — a dlpack import left
    on the CPU backend would drag every program fed from it there."""
    want = {jax.devices()[0]}
    cols = _columns()
    for raw, dlpack in (
        (RawColumns.from_columns(cols), True),
        (RawColumns.from_columns(cols), False),
        (RawColumns.from_matrix(np.column_stack(cols)), True),
    ):
        assert to_device(raw, dlpack=dlpack).devices() == want
        assert to_device(raw, padded_rows=8, dlpack=dlpack).devices() == want
    assert ingest_stats()["dlpack_transfers"] == 2


def test_unexportable_columns_take_the_host_rung_by_inspection():
    """dlpack cannot export read-only buffers (float32 columns decoded
    zero-copy out of an Arrow body) or strided views: both are detected
    up front and counted under a fixed reason, not an exception name."""
    readonly = [np.arange(4, dtype=np.float32) for _ in range(2)]
    for col in readonly:
        col.setflags(write=False)
    strided = [np.arange(8, dtype=np.float32)[::2] for _ in range(2)]
    for cols in (readonly, strided):
        X = np.asarray(to_device(RawColumns.from_columns(cols), dlpack=True))
        np.testing.assert_array_equal(X, np.column_stack(cols))
    stats = ingest_stats()
    assert stats["dlpack_transfers"] == 0
    assert stats["fallback_reasons"] == {
        "readonly_column": 1,
        "non_contiguous_column": 1,
    }


def test_transfer_refusal_propagates(monkeypatch):
    """A refusal from the transfer itself is a fault on the device path:
    it must surface, not be absorbed as a counted fallback."""

    def broken(*_args, **_kwargs):
        raise RuntimeError("dlpack unavailable on this backend")

    monkeypatch.setattr(jax.dlpack, "from_dlpack", broken)
    with pytest.raises(RuntimeError, match="dlpack unavailable"):
        to_device(RawColumns.from_columns(_columns()), dlpack=True)
    assert ingest_stats()["fallback_reasons"] == {}


def test_f64_columns_cast_and_transfer():
    cols = [np.arange(4, dtype=np.float64) for _ in range(2)]
    X = np.asarray(to_device(RawColumns.from_columns(cols), dlpack=True))
    assert X.dtype == np.float32
    np.testing.assert_array_equal(X, np.column_stack(cols).astype(np.float32))
    assert ingest_stats()["dlpack_transfers"] == 1
