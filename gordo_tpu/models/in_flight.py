"""
Parameters on their way from the device to the host.

A fit whose parameters are a large artifact does not wait for them
(``FleetTrainer._collect_results``, parallel/fleet.py): it starts every
leaf's transfer, in the order a pickler walks the tree, and hands each
member a :class:`LeafInFlight` where the eager schedule hands it a
``numpy`` array. Whoever needs a leaf's bytes takes it and waits for that
leaf alone. The first to need them is the pickler inside
``serializer.dump``: it reaches leaf *k*, takes it, and while leaf *k* is
hashed and written leaves *k+1...* arrive, so the artifact pays the
longer of the link and the md5 and not their sum. A leaf pickles to the
opcodes of the array it becomes: ``model.pkl`` is the eager schedule's
byte for byte.

A leaf's copy on the device goes when its copy on the host is there,
so a flight holds its fit's whole parameter block on the chip until
somebody takes its leaves. Nothing may run on the chip beside that
block which the eager schedule ran without it: whoever starts more
device work lands the flights before it first (:meth:`Flight.land`;
``FleetTrainer`` before a bucket's fit, ``FleetBuilder`` before a final
fit and before the sequential builder). The copies are on the host
well under a second after they were started, so that wait is what the
eager schedule's ``collect`` paid, and only the last fit's flight is
still aloft when the dump begins.

The builder lands what is left before it returns
(``FleetBuilder._land_parameters``): nothing outside a build sees the
type.
"""

import logging
import threading
import time
from typing import Any, List, Optional

import jax
import numpy as np

logger = logging.getLogger(__name__)


class Flight:
    """One fit's transfers, counted for the spans that tell of them:
    ``bytes_started`` at the ``collect`` that started them, and what
    takers found (``bytes_landed``) and waited (``wait_seconds``) since,
    which a caller reads before and after the work it accounts for
    (``FleetBuilder._dump_all``)."""

    def __init__(self) -> None:
        self.bytes_started = 0
        self.bytes_landed = 0
        self.wait_seconds = 0.0
        self._transfers: List["_Transfer"] = []
        self._lock = threading.Lock()

    def start(self, leaf, writable: bool) -> "_Transfer":
        self.bytes_started += int(leaf.nbytes)
        transfer = _Transfer(self, leaf, writable)
        self._transfers.append(transfer)
        return transfer

    @property
    def aloft(self) -> bool:
        """Whether a leaf is yet to be taken, and so still on the device."""
        return any(transfer._host is None for transfer in self._transfers)

    def land(self) -> None:
        """Every leaf waited for, so that none of the block is left on
        the device: for whoever is about to run something else there.
        It does not need the bytes, so an error of a transfer is not
        its to raise: the leaf's taker meets it again."""
        errors = []
        for transfer in self._transfers:
            try:
                transfer.host()
            except Exception as exc:
                errors.append(exc)
        if errors:
            logger.warning(
                "%d of %d parameter leaves did not land, the first with %r",
                len(errors),
                len(self._transfers),
                errors[0],
            )

    def _landed(self, nbytes: int, waited: float) -> None:
        with self._lock:
            self.bytes_landed += nbytes
            self.wait_seconds += waited


class _Transfer:
    """One stacked leaf of a fit program's parameters, its copy to the
    host started here. The device's copy goes when the host's is there.
    ``writable``: the eager schedule hands this leaf out as a copy of
    its own in C order (``fetch_to_host`` coalesces it) and not as the
    runtime's array, which is read-only and laid out as the device had
    it (on a TPU some leaves come back in Fortran order), and a pickle
    tells the two apart: a writable buffer is written as ``BYTEARRAY8``
    and a read-only one as ``BINBYTES8``, each in its array's order."""

    def __init__(self, flight: Flight, leaf, writable: bool) -> None:
        leaf.copy_to_host_async()
        self._flight = flight
        self._device: Optional[Any] = leaf
        self._writable = writable
        self._host: Optional[np.ndarray] = None
        self._lock = threading.Lock()

    def host(self) -> np.ndarray:
        """The leaf on the host, waited for once. An error of the device
        or the link is raised to the taker that met it and nothing is
        kept: the next one asks the runtime again."""
        with self._lock:
            if self._host is None:
                began = time.perf_counter()
                host = np.asarray(self._device)
                self._flight._landed(host.nbytes, time.perf_counter() - began)
                if self._writable:
                    if host.flags.owndata and host.flags.c_contiguous:
                        # nobody else has this array once the device's
                        # copy, which caches it, goes below
                        host.flags.writeable = True
                    else:  # the device's order, or memory the runtime keeps
                        host = np.array(host, order="C")
                self._host, self._device = host, None
            return self._host


class LeafInFlight:
    """One member's row of a stacked leaf whose transfer was started and
    not waited for. ``take()`` is the ``numpy`` array the eager schedule
    would have handed out (a view of the stacked leaf's host copy), and
    the pickle of a leaf is that array's."""

    __slots__ = ("_transfer", "_row")

    def __init__(self, transfer: _Transfer, row: int) -> None:
        self._transfer, self._row = transfer, row

    def take(self) -> np.ndarray:
        # asarray: a row of a leaf of scalars is a scalar, and 0-d there
        return np.asarray(self._transfer.host()[self._row])

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.take(), dtype=dtype)

    def __reduce_ex__(self, protocol):
        # the array's own reduce, so the pickler writes the array's
        # opcodes and memoizes one object, as it does for the array
        return self.take().__reduce_ex__(protocol)


def _in_flight(leaf) -> bool:
    return isinstance(leaf, LeafInFlight)


def landed(params):
    """``params`` with every leaf on its way taken: plain ``numpy``."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.take() if _in_flight(leaf) else leaf, params
    )


def for_pickling(params):
    """``params`` as an estimator's pickle holds them: every leaf a host
    ``numpy`` array, but a leaf on its way, which stays what it is until
    the pickler reaches it."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    # gt-lint: disable=jax-device-sync -- pickling fetch on the
    # serialization path, not timed device work; no span exists
    fetched = iter(jax.device_get([l for l in leaves if not _in_flight(l)]))
    return treedef.unflatten(
        [l if _in_flight(l) else np.asarray(next(fetched)) for l in leaves]
    )
