"""
A machine's ``get_data()`` in a worker process.

A fleet build fetches its machines on ``data_workers`` threads, and a
fetch is pandas and numpy in steps too small to leave the interpreter
lock for long: sixteen threads of one interpreter computed on 2.4 of the
chip host's 13 cores and waited for the lock 85% of their seconds
(``PERF.md`` 5, PR 40's traced ``hourglass_build`` job: 7.0 s of a 14.3 s
job). So the threads stay, one a machine in flight, and keep everything
that counts or decides (fault points, retries, the deadline, the span);
what a thread hands to a worker of this pool is the computing alone: the
pickled dataset over, ``(X, y)`` and what ``get_data()`` left on the
dataset back.

**The workers** are fresh interpreters (never ``fork``: the parent
holds the accelerator's runtime and its threads) started on
:func:`serve` by ``subprocess``, not by ``multiprocessing``'s ``spawn``,
which imports the parent's ``__main__`` into every child: the builder's
is a program that holds the chip. They import this package's dataset
layer and nothing else of it: no jax (the parent owns the chip;
``JAX_PLATFORMS=cpu`` in a worker's environment says so to a provider
that would import it) and no sklearn (``serializer/import_utils.py`` has
why: three of the four seconds a worker's start would take). Each talks
to the parent over a
socket pair of its own, so a worker whose parent is gone, however it
went, reads end-of-file and exits. They are kept between the jobs of a
process, as ``parallel/host_blocks.py`` keeps its buffers: a process's
first job over the line pays the start (:func:`ensure`), the next ones
find the pool up.

**The way back** is pickle protocol 5 with the frames' values out of
band, received straight into the buffers the parent's frames are then
built on: nothing is copied element by element. A frame of one numeric
dtype, which is what a ``TimeSeriesDataset`` returns, crosses as its
parts (:func:`_pack`): the block's bytes, its dtype, shape and memory
order, and the index and columns as they pickle, so that what the parent
builds is the frame ``get_data()`` returned (dtypes, index with its tz,
unit and freq, column order, values to the bit, the block laid out as it
was) on numpy's own dtype objects. The last matters: an array that numpy
unpickles carries a copy of its dtype, every array computed from it
inherits the copy, and a ``model.pkl`` written downstream would hold one
more dtype definition than the same model built in-thread: the same
model, another md5. Where ``y`` is ``X`` again (an autoencoder's
dataset), its values cross once.

**There is nothing to set.** Which path a job takes is read off the job
(:func:`wanted`), and which a dataset takes off the dataset
(:func:`crossing`).
"""

import atexit
import logging
import os
import pickle
import queue
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from .datasets import TimeSeriesDataset

logger = logging.getLogger(__name__)

#: The fewest machines for which a process that has no pool yet starts
#: one. Measured on the chip's host (13 cores; my chip runs, PR 42,
#: ``PERF.md`` 6): the start is 1.5 s for one worker and 2.2-2.5 s for
#: twelve side by side (an interpreter and the import of pandas, 2.1 CPU
#: seconds a worker there), paid once a process; the phase takes 44-51 ms
#: a machine of ``hourglass_build``'s documents on sixteen threads and
#: 12-16 in the pool, 76-79 and 6-7 of ``lstm_build``'s: a machine saves
#: 33-72 ms. So a process's FIRST job gets its start back only from 31-67
#: machines on, and every later job of the process gains at once (0.5-1.1
#: s a job of sixteen machines). The line is therefore not the first
#: job's break-even: it is the highest that keeps ``lstm_build``'s sixteen
#: machines, built job after job in one process, on the pool's side. A
#: process that builds one fleet of 16-30 machines and exits loses up to
#: 1.7 s to it, beside cold compiles of a minute; fleets under the line
#: (tests, examples, a few machines rebuilt) start nothing.
MIN_MACHINES = 16

#: A dataset whose pickle is larger than this holds its data itself (a
#: ``ListBackedDataProvider`` over a caller's series, a file provider that
#: has read its file): copying that to a worker costs what the worker
#: would save. A dataset that names its source pickles to a few KB.
CROSSING_LIMIT_BYTES = 1 << 20

#: a worker that has not said hello by then (its imports hang) is ended
START_TIMEOUT_SECONDS = 120.0

#: what ``get_data()`` leaves on a ``TimeSeriesDataset`` beside its
#: return value, and the parent's copy of the dataset therefore needs
FETCH_STATE = ("_metadata", "fetch_seconds", "fetch_cpu_seconds")


class CannotCross(Exception):
    """This dataset is not fetched in a worker: it does not load there
    (its class is not importable by name in a fresh interpreter), or no
    worker is up. The caller fetches it on its own thread."""


class WorkerLost(RuntimeError):
    """The worker died or stopped making sense in the middle of a fetch.
    The fetch may be tried again; the worker is gone from the pool."""


class Fetched(NamedTuple):
    """One ``get_data()`` as it came back from a worker."""

    X: pd.DataFrame
    y: pd.DataFrame
    #: the ``FETCH_STATE`` attributes of the worker's dataset
    state: Dict[str, Any]
    #: the worker's wall and CPU seconds for the call
    seconds: float
    cpu_seconds: float
    #: the bytes that crossed back
    nbytes: int


# ------------------------------------------------------------------ the wire
#
# A message is a pickle and the buffers it left out of band (PEP 574):
# a header of unsigned 64-bit counts (the pickle's bytes, the number of
# buffers, each buffer's bytes), then the pickle, then the buffers.


def _send(
    sock: socket.socket, body: bytes, buffers: Sequence[pickle.PickleBuffer] = ()
) -> None:
    views = [buffer.raw() for buffer in buffers]
    sock.sendall(
        struct.pack(
            f"<{2 + len(views)}Q", len(body), len(views), *(v.nbytes for v in views)
        )
    )
    sock.sendall(body)
    for view in views:
        sock.sendall(view)


def _recv_exact(sock: socket.socket, nbytes: int) -> bytearray:
    data = bytearray(nbytes)
    view, got = memoryview(data), 0
    while got < nbytes:
        count = sock.recv_into(view[got:])
        if not count:
            raise EOFError("the other end of a fetch worker's socket is closed")
        got += count
    return data


def _recv(sock: socket.socket) -> Tuple[bytearray, List[bytearray], int]:
    """A message's pickle, its buffers (writable, as the arrays built on
    them will be) and the bytes it took in all."""
    body_size, count = struct.unpack("<2Q", _recv_exact(sock, 16))
    sizes = struct.unpack(f"<{count}Q", _recv_exact(sock, 8 * count))
    body = _recv_exact(sock, body_size)
    buffers = [_recv_exact(sock, size) for size in sizes]
    return body, buffers, 16 + 8 * count + body_size + sum(sizes)


# ---------------------------------------------------------------- the worker


def _block(frame: Any) -> Optional[np.ndarray]:
    """The values of a frame of one numeric dtype, as the frame holds
    them (no copy); None for anything else."""
    if not isinstance(frame, pd.DataFrame) or frame._mgr.nblocks != 1:
        return None
    values = frame.to_numpy()
    if values.dtype.kind not in "fiub" or values.dtype.itemsize not in (1, 2, 4, 8):
        return None
    return values


def _is_again(X: Any, y: Any) -> bool:
    """Whether ``y`` is ``X`` again: a frame of the same index, columns,
    dtype, memory order and bits, as a dataset whose targets are its tags
    returns."""
    mine, other = _block(X), _block(y)
    if mine is None or other is None:
        return False
    if not (
        mine.dtype == other.dtype
        and mine.strides == other.strides
        and X.index.identical(y.index)
        and X.columns.identical(y.columns)
    ):
        return False
    bits = f"u{mine.dtype.itemsize}"  # equal to the bit, not as floats
    return bool(np.array_equal(mine.view(bits), other.view(bits)))


def _pack(frame: Any) -> Any:
    """A frame of one numeric dtype as its parts, the values to go out
    of band; any other object as it is, to be pickled whole. A block that
    is contiguous neither way (a reversed selection of columns) crosses
    row-major."""
    values = _block(frame)
    if values is None:
        return frame
    order = "F" if values.flags.f_contiguous and not values.flags.c_contiguous else "C"
    flat = values.T if order == "F" else np.ascontiguousarray(values)
    return {
        "values": pickle.PickleBuffer(flat),
        "dtype": values.dtype.str,
        "shape": values.shape,
        "order": order,
        "index": frame.index,
        "columns": frame.columns,
    }


def _unpack(packed: Any, copy: bool = False) -> Any:
    """The frame :func:`_pack` took apart, on the buffer that was
    received (or, ``copy``, on a copy of it: ``y`` where it is ``X``
    again)."""
    if not isinstance(packed, dict):
        return packed
    buffer = bytearray(packed["values"]) if copy else packed["values"]
    values = np.ndarray(
        packed["shape"], np.dtype(packed["dtype"]), buffer, order=packed["order"]
    )
    return pd.DataFrame(
        values, index=packed["index"], columns=packed["columns"], copy=False
    )


def _portable(exc: BaseException) -> BaseException:
    """``exc`` if it survives a pickle as what it is, else a
    ``RuntimeError`` that says what it was; either way with the worker's
    traceback as a note."""
    note = "in a fetch worker:\n" + "".join(traceback.format_exception(exc))
    try:
        if type(pickle.loads(pickle.dumps(exc))) is not type(exc):
            raise TypeError(type(exc))
    except Exception:  # noqa: BLE001 - whatever the exception's pickle raises
        exc = RuntimeError(f"{type(exc).__name__}: {exc}")
    exc.__traceback__ = None
    exc.add_note(note.rstrip())
    return exc


def _answer(request: bytearray) -> Tuple[bytes, List[pickle.PickleBuffer]]:
    """One request (a pickled dataset) to one reply."""
    began, cpu_began = time.perf_counter(), time.process_time()

    def clocks() -> Dict[str, float]:
        return {
            "seconds": time.perf_counter() - began,
            "cpu_seconds": time.process_time() - cpu_began,
        }

    try:
        dataset = pickle.loads(request)
    except Exception as exc:  # noqa: BLE001 - any failure to load means: not here
        return pickle.dumps({"cannot_cross": repr(exc), **clocks()}), []
    try:
        X, y = dataset.get_data()
    except Exception as exc:  # noqa: BLE001 - the parent raises it as its own
        return pickle.dumps({"raised": _portable(exc), **clocks()}), []
    reply = {
        "X": _pack(X),
        "y": None if _is_again(X, y) else _pack(y),
        "state": {
            name: getattr(dataset, name)
            for name in FETCH_STATE
            if hasattr(dataset, name)
        },
        **clocks(),
    }
    buffers: List[pickle.PickleBuffer] = []
    return pickle.dumps(reply, protocol=5, buffer_callback=buffers.append), buffers


def serve(fd: int) -> None:
    """A worker's whole life: say hello (its start's CPU seconds), then
    answer requests until the parent's end of the socket closes."""
    signal.signal(signal.SIGINT, signal.SIG_IGN)  # the parent decides
    sock = socket.socket(fileno=fd)
    try:
        _send(sock, pickle.dumps({"cpu_seconds": time.process_time()}))
        while True:
            request, _, _ = _recv(sock)
            _send(sock, *_answer(request))
    except (EOFError, OSError):
        pass
    finally:
        sock.close()


# ------------------------------------------------------------------ the pool


class _Worker:
    def __init__(self) -> None:
        mine, theirs = socket.socketpair()
        try:
            self.process = subprocess.Popen(
                [
                    sys.executable,
                    "-c",
                    "from gordo_tpu.dataset.fetch_pool import serve; "
                    f"serve({theirs.fileno()})",
                ],
                pass_fds=(theirs.fileno(),),
                stdin=subprocess.DEVNULL,
                env={
                    **os.environ,
                    # what the parent can import by name, the worker can
                    "PYTHONPATH": os.pathsep.join(p for p in sys.path if p),
                    "JAX_PLATFORMS": "cpu",
                },
            )
        except BaseException:
            mine.close()
            raise
        finally:
            theirs.close()
        self.sock = mine

    def close(self) -> None:
        """End the worker: it exits at the end-of-file; killed if not."""
        self.sock.close()
        try:
            self.process.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            self.process.kill()
            self.process.wait()


_lock = threading.Lock()
_workers: List[_Worker] = []
_idle: "queue.Queue[_Worker]" = queue.Queue()
#: the CPU seconds the workers have reported: their starts and their calls
_cpu_seconds = 0.0


def cores() -> int:
    """The cores this process may run on."""
    return len(os.sched_getaffinity(0))


def size() -> int:
    """The workers that are up."""
    with _lock:
        return len(_workers)


def cpu_seconds() -> float:
    """The CPU seconds the workers have spent for this process so far, as
    they reported them: beside ``time.process_time()``, which does not see
    them, what the process's work has cost."""
    with _lock:
        return _cpu_seconds


def wanted(machines: int) -> bool:
    """Whether a job that fetches ``machines`` machines uses the pool: one
    machine never (nothing to run beside it); ``MIN_MACHINES`` or more
    always; and any number between once the pool is up, which costs
    nothing more then."""
    return machines >= MIN_MACHINES or (machines >= 2 and size() > 0)


def workers_for(data_workers: int, machines: int) -> int:
    """The workers a job may keep busy: its fetches in flight, its
    machines, and the cores but one, which is the parent's."""
    return max(1, min(data_workers, cores() - 1, machines))


def ensure(count: int) -> int:
    """Bring the pool up to ``count`` workers; returns how many were
    started (0 where the pool had them already). They start side by
    side, so it takes one worker's start: an interpreter and the import
    of pandas."""
    global _cpu_seconds
    with _lock:
        fresh: List[_Worker] = []
        try:
            for _ in range(count - len(_workers)):
                fresh.append(_Worker())
        except OSError as exc:  # no interpreter to start, no process to have
            logger.warning("Fetch workers not started: %r", exc)
        started = []
        for worker in fresh:
            try:
                worker.sock.settimeout(START_TIMEOUT_SECONDS)
                hello, _, _ = _recv(worker.sock)
                worker.sock.settimeout(None)
                _cpu_seconds += pickle.loads(hello)["cpu_seconds"]
            except Exception as exc:  # noqa: BLE001 - it did not come up; the rest may
                logger.warning("A fetch worker did not come up: %r", exc)
                worker.close()
                continue
            started.append(worker)
        _workers.extend(started)
    for worker in started:
        _idle.put(worker)
    return len(started)


def _drop(worker: _Worker) -> None:
    with _lock:
        if worker in _workers:
            _workers.remove(worker)
    worker.close()


def crossing(dataset: Any) -> Optional[bytes]:
    """``dataset`` as it crosses to a worker, or None where it stays: it
    is no ``TimeSeriesDataset`` (whose ``FETCH_STATE`` is all its
    ``get_data()`` leaves behind), it or its provider does not pickle, or
    it carries its data with it (``CROSSING_LIMIT_BYTES``)."""
    if not isinstance(dataset, TimeSeriesDataset):
        return None
    try:
        request = pickle.dumps(dataset, protocol=5)
    except Exception:  # noqa: BLE001 - a lock, a client, a local class: anything
        return None
    return request if len(request) <= CROSSING_LIMIT_BYTES else None


def fetch(request: bytes) -> Fetched:
    """``get_data()`` of the dataset pickled in ``request`` on the next
    free worker; the calling thread waits for it. Raises what
    ``get_data()`` raised there, as the same type; ``CannotCross`` where
    the dataset does not load there or no worker is up; ``WorkerLost``
    where the worker died under it."""
    global _cpu_seconds
    while True:
        try:
            worker = _idle.get(timeout=0.5)
            break
        except queue.Empty:
            if not size():
                raise CannotCross("no fetch worker is up") from None
    try:
        _send(worker.sock, request)
        body, buffers, nbytes = _recv(worker.sock)
        reply = pickle.loads(body, buffers=buffers)
    except BaseException as exc:
        _drop(worker)
        if isinstance(exc, (EOFError, OSError, pickle.UnpicklingError)):
            raise WorkerLost(f"fetch worker {worker.process.pid}: {exc!r}") from exc
        raise
    _idle.put(worker)
    with _lock:
        _cpu_seconds += reply["cpu_seconds"]
    if "cannot_cross" in reply:
        raise CannotCross(reply["cannot_cross"])
    if "raised" in reply:
        raise reply["raised"]
    return Fetched(
        _unpack(reply["X"]),
        _unpack(reply["X"], copy=True) if reply["y"] is None else _unpack(reply["y"]),
        reply["state"],
        reply["seconds"],
        reply["cpu_seconds"],
        nbytes,
    )


def shutdown() -> None:
    """End every worker (the process's exit; tests). Call it with no
    fetch in flight."""
    with _lock:
        workers = _workers[:]
        del _workers[:]
    while True:
        try:
            _idle.get_nowait()
        except queue.Empty:
            break
    for worker in workers:
        worker.close()


atexit.register(shutdown)
