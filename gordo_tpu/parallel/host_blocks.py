"""
Staging blocks on pages the host already has.

A build stacks its members into host blocks, hands them to
``jax.device_put`` and is done with them when the program that read them
has answered. Allocated anew each time (``np.zeros``), every page of such
a block is faulted in as it is first written, and on the hosts this runs
on that fault, not the copy, sets the pace: on the chip's host the same
fill runs at about 1 GB/s into fresh pages and at 5 GB/s (a build's
column-major members) to 13 (a plain copy) into pages touched before
(``PERF.md`` 5, 6). So the blocks are leased from one process-wide pool of
flat buffers and go back to it: ``cv_train``'s pages serve ``final_fit``,
``cv_predict`` and ``cv_score`` of the same job, and the next job's
``cv_train``.

There is nothing to set. Whether a block is reused is read off what the
pool holds: the smallest free buffer that is large enough, else a new one.
The pool keeps no more than a job used: :func:`trim`, at the end of
``FleetBuilder.build``, drops every buffer no lease gave back since the
trim before.

**When a block may go back.** ``device_put`` of a host array returns
before the transfer has ended on an accelerator, and on the CPU backend
the device array may alias the host buffer outright. So a lease ends only
when the program that consumed the put arrays has handed its results to
the host, and nothing that is read later was put from the block. A lease
that ends in an exception gives nothing back: what raised may have left a
transfer in flight, and the buffers go with their last reference instead.
"""

import contextlib
import threading
from typing import Iterable, Iterator, List, Tuple

import numpy as np

_lock = threading.Lock()
#: the flat byte buffers nobody leases: those a lease gave back since the
#: last :func:`trim`, and those that have lain here since the trim before
_given_back: List[np.ndarray] = []
_idle: List[np.ndarray] = []


def _take(nbytes: int) -> Tuple[np.ndarray, bool]:
    """A buffer of at least ``nbytes``, the smallest the pool holds, and
    whether it is instead a new one (all zeros, no page touched)."""
    with _lock:
        fitting = [
            (buffer.nbytes, i, held)
            for held in (_given_back, _idle)
            for i, buffer in enumerate(held)
            if buffer.nbytes >= nbytes
        ]
        if fitting:
            _, i, held = min(fitting, key=lambda found: found[0])
            return held.pop(i), False
    return np.zeros(nbytes, np.uint8), True


class Lease:
    """The staging blocks of one program: each a view of the asked shape
    and dtype over a pool buffer, holding exactly what ``np.zeros`` and
    the caller's writes would have left there. ``bytes_reused`` counts the
    bytes of the blocks that lie in buffers the pool already held (the
    ``stack`` part's counter of that name)."""

    def __init__(self):
        self._buffers: List[np.ndarray] = []
        self.bytes_reused = 0

    def _block(self, shape, dtype) -> Tuple[np.ndarray, bool]:
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if not nbytes:
            return np.zeros(shape, dtype), True
        buffer, fresh = _take(nbytes)
        self._buffers.append(buffer)
        if not fresh:
            self.bytes_reused += nbytes
        return buffer[:nbytes].view(dtype).reshape(shape), fresh

    def zeros(self, shape, dtype=np.float32) -> np.ndarray:
        """A block of zeros (the weight planes, which are written whole)."""
        block, fresh = self._block(shape, dtype)
        if not fresh:
            block.fill(0)
        return block

    def stacked(
        self, shape, members: Iterable[np.ndarray], dtype=np.float32
    ) -> np.ndarray:
        """A block ``[M, N, ...]`` whose member ``i`` holds ``members[i]``
        from its first row on, and zeros everywhere else: a member's rows
        past its end (the sample padding) and the members past the last
        (the zero-weight dummies). One copy a member; in a buffer that was
        held before, the padding is what has to be written beside it, and
        no more than that."""
        block, fresh = self._block(shape, dtype)
        count = 0
        for rows in members:
            block[count, : len(rows)] = rows
            if not fresh:
                block[count, len(rows) :] = 0
            count += 1
        if not fresh:
            block[count:] = 0
        return block


@contextlib.contextmanager
def lease() -> Iterator[Lease]:
    """The blocks of one program, from before they are filled until its
    results are on the host; then, and only on the way out without an
    exception, their buffers go back to the pool."""
    blocks = Lease()
    yield blocks
    with _lock:
        _given_back.extend(blocks._buffers)


def trim() -> None:
    """Drop every free buffer that no lease gave back since the last call:
    the end of a build, so that the pool holds what that build used."""
    with _lock:
        _idle[:] = _given_back
        del _given_back[:]


def clear() -> None:
    """Drop every free buffer (tests: the next lease finds nothing)."""
    with _lock:
        del _given_back[:], _idle[:]


def free_nbytes() -> int:
    """The bytes of the buffers no lease holds."""
    with _lock:
        return sum(buffer.nbytes for buffer in _given_back + _idle)
