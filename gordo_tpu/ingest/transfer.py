"""
Wire-column → device transfer without the host staging copy.

The legacy decode path materializes a request as ``np.column_stack`` of
the Arrow wire columns — a full host copy of the payload — and only then
hands the matrix to the device program, which copies it AGAIN across the
transfer boundary. :class:`RawColumns` instead carries the decoded wire
columns as-is (zero-copy views straight out of the Arrow buffers) and
:func:`to_device` moves them per-column over the dlpack protocol onto
the serving device, so the first full-matrix materialization happens
device-side inside ``stack``.

Which rung a request takes is decided by LOOKING at its columns, never
by catching an exception: dlpack cannot export a read-only or
non-contiguous buffer (numpy refuses both), so such columns — float32
columns decoded zero-copy out of an Arrow body are read-only — take the
host rung, ``host_matrix()`` + one transfer, with the reason counted. A
refusal from the transfer itself is a fault and propagates. Outcomes are
counted module-wide (:func:`ingest_stats`) so benches and
``/fleet-health`` can see which rung actually served traffic.
"""

import threading
from typing import Any, Optional, Sequence

import numpy as np

_stats_lock = threading.Lock()
_STATS = {
    "dlpack_transfers": 0,
    "host_transfers": 0,
    "dlpack_columns": 0,
    "fallback_reasons": {},
}


def _note_transfer(dlpack: bool, columns: int = 0, reason: str = "") -> None:
    with _stats_lock:
        if dlpack:
            _STATS["dlpack_transfers"] += 1
            _STATS["dlpack_columns"] += columns
        else:
            _STATS["host_transfers"] += 1
            if reason:
                reasons = _STATS["fallback_reasons"]
                reasons[reason] = reasons.get(reason, 0) + 1


def ingest_stats() -> dict:
    """Process-wide transfer counters: how many requests went over
    dlpack vs the host staging path, and why the host path was taken."""
    with _stats_lock:
        return {
            "dlpack_transfers": _STATS["dlpack_transfers"],
            "host_transfers": _STATS["host_transfers"],
            "dlpack_columns": _STATS["dlpack_columns"],
            "fallback_reasons": dict(_STATS["fallback_reasons"]),
        }


def reset_ingest_stats() -> None:
    with _stats_lock:
        _STATS["dlpack_transfers"] = 0
        _STATS["host_transfers"] = 0
        _STATS["dlpack_columns"] = 0
        _STATS["fallback_reasons"] = {}


class RawColumns:
    """A request payload still in wire form: per-feature columns in
    model-tag order, not yet stacked into a matrix.

    Built from decoded Arrow columns (zero-copy buffer views) or, for
    JSON/fallback requests, from an existing matrix (``matrix`` mode —
    already staged, nothing to save, but it lets every caller speak one
    payload type). ``host_matrix()`` is the escape hatch back to the
    legacy staged ``float32`` matrix and is lazy: the raw-column fast
    path never pays for it.

    >>> raw = RawColumns.from_columns(
    ...     [np.array([1.0, 2.0]), np.array([3.0, 4.0])])
    >>> raw.rows, raw.width
    (2, 2)
    >>> raw.host_matrix().shape
    (2, 2)
    """

    __slots__ = ("columns", "matrix", "rows", "width", "_host")

    def __init__(
        self,
        columns: Optional[Sequence[np.ndarray]],
        matrix: Optional[np.ndarray],
        rows: int,
        width: int,
    ):
        self.columns = tuple(columns) if columns is not None else None
        self.matrix = matrix
        self.rows = int(rows)
        self.width = int(width)
        self._host: Optional[np.ndarray] = None

    @classmethod
    def from_columns(cls, columns: Sequence[np.ndarray]) -> "RawColumns":
        cols = [np.asarray(col) for col in columns]
        rows = len(cols[0]) if cols else 0
        return cls(cols, None, rows, len(cols))

    @classmethod
    def from_matrix(cls, matrix: Any) -> "RawColumns":
        mat = np.asarray(matrix)
        return cls(None, mat, mat.shape[0], mat.shape[1] if mat.ndim > 1 else 1)

    def host_matrix(self) -> np.ndarray:
        """The legacy staged matrix (``float32``, C-order), built at most
        once."""
        if self._host is None:
            if self.matrix is not None:
                self._host = np.ascontiguousarray(self.matrix, np.float32)
            else:
                self._host = np.column_stack(
                    [np.asarray(col, np.float32) for col in self.columns]
                )
        return self._host

    @property
    def nbytes(self) -> int:
        if self.columns is not None:
            return int(sum(col.nbytes for col in self.columns))
        return int(self.matrix.nbytes)


def serving_device() -> Any:
    """The device the serving programs run on: the default device,
    where the fleet store's uncommitted bucket params live."""
    import jax

    return jax.devices()[0]


def _dlpack_refusal(columns: Sequence[np.ndarray]) -> str:
    """Why ``columns`` cannot cross over dlpack, or ``""`` when they
    can. float32 columns cross as they are and must be exportable;
    anything else is cast first, and the cast's copy always is."""
    for col in columns:
        if col.dtype != np.float32:
            continue
        if not col.flags["C_CONTIGUOUS"]:
            return "non_contiguous_column"
        if not col.flags["WRITEABLE"]:
            return "readonly_column"
    return ""


def _dlpack_column(col: np.ndarray, device: Any) -> Any:
    """One wire column onto ``device`` via dlpack, as float32."""
    import jax

    if col.dtype != np.float32:
        # dlpack moves bytes, not values: cast (a copy) first. Arrow f64
        # wires land here; the compiled path computes f32 regardless.
        col = np.ascontiguousarray(col, np.float32)
    # without ``device`` the import stays committed to the CPU backend
    # the numpy buffer lives on, and every program fed from it follows
    # it there
    return jax.dlpack.from_dlpack(col, device=device)


def to_device(
    raw: RawColumns,
    padded_rows: Optional[int] = None,
    dlpack: bool = True,
) -> Any:
    """``raw`` as a ``[rows, width]`` (or ``[padded_rows, width]``)
    float32 array on :func:`serving_device`.

    Fast rung: each wire column crosses via dlpack and the matrix is
    first assembled device-side (``jnp.stack(axis=1)``); row padding, if
    any, happens on device too. Host rung (``dlpack=False``, a
    matrix-mode payload, or columns dlpack cannot export — see
    :func:`_dlpack_refusal`): the legacy host staging —
    ``host_matrix()`` zero-padded on host, one transfer. Both rungs
    return the same values on the same device; only the copy count
    differs.
    """
    import jax
    import jax.numpy as jnp

    device = serving_device()
    rows = raw.rows
    target = padded_rows if padded_rows is not None else rows
    if not dlpack:
        reason = "disabled"
    elif raw.columns is None or raw.width == 0 or rows == 0:
        reason = "no_columns"
    else:
        reason = _dlpack_refusal(raw.columns)
    if not reason:
        X = jnp.stack(
            [_dlpack_column(col, device) for col in raw.columns], axis=1
        )
        if target != rows:
            X = jnp.zeros((target, raw.width), jnp.float32).at[:rows].set(X)
        _note_transfer(True, columns=raw.width)
        return X
    _note_transfer(False, reason=reason)
    host = raw.host_matrix()
    if target != rows:
        padded = np.zeros((target, raw.width), np.float32)
        padded[:rows] = host
        host = padded
    return jax.device_put(host, device)
