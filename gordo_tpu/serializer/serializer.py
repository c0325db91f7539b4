"""
Disk / bytes serialization of trained models.

Artifact layout parity with gordo/serializer/serializer.py:149-196: a model
directory holds ``model.pkl`` (the pickled estimator/pipeline),
``metadata.json`` and ``info.json`` (with the model file's checksum). The
pickle-bytes form (``dumps``/``loads``) is the wire format of the server's
``/download-model`` route.

JAX estimators make this work by storing their params as host numpy arrays in
``__getstate__`` (see gordo_tpu/models/estimators.py), so a pickled model is
device-independent and loads on any backend.
"""

import hashlib
import logging
import os
import pickle
import re
import shutil
import threading
import uuid
from os import path
from typing import Any, Callable, NamedTuple, Optional

from ..telemetry.aggregate import ROLLUP_DIR, is_worker_variant
from ..telemetry.fleet_health import FLEET_HEALTH_FILE, FLEET_HEALTH_SHARD_DIR
from ..telemetry.progress import BUILD_STATUS_FILE, BUILD_TRACE_FILE
from ..telemetry.serving import SERVE_TRACE_FILE
from ..telemetry.slo import SLO_CONFIG_FILE, SLO_STATE_FILE
from ..utils import json_compat as simplejson
from ..utils.faults import fault_point

logger = logging.getLogger(__name__)

MODEL_FILE = "model.pkl"
METADATA_FILE = "metadata.json"
INFO_FILE = "info.json"

#: ``model.pkl``'s pickle protocol: numpy hands a contiguous leaf to a
#: protocol-5 pickler as a ``PickleBuffer``, and the C pickler passes a
#: buffer larger than its 64 KiB frame straight to ``write()``, so a
#: backbone's leaf reaches the file as a view of the array's own memory
#: (in-band: one file, no ``buffer_callback``). Every Python this
#: package supports reads it, and artifacts of earlier builds
#: (protocol 4) load unchanged.
MODEL_PICKLE_PROTOCOL = 5

#: a buffer at least this large is hashed on a helper thread while the
#: calling thread writes it (``hashlib`` and file writes both release
#: the GIL); a smaller one (the pickle's frames, every byte of a dense
#: member's few KB) is not worth a thread and is hashed in line
HASH_BESIDE_WRITE_MIN_BYTES = 1 << 20


def dumps(model) -> bytes:
    """
    Serialize a model into bytes.

    >>> from sklearn.preprocessing import MinMaxScaler
    >>> restored = loads(dumps(MinMaxScaler(feature_range=(0, 2))))
    >>> restored.feature_range
    (0, 2)
    """
    return pickle.dumps(model)


def loads(bytes_object: bytes):
    """Restore a model serialized with ``dumps``."""
    return pickle.loads(bytes_object)


def _file_checksum(file_path: str) -> str:
    digest = hashlib.md5()
    with open(file_path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            digest.update(chunk)
    return digest.hexdigest()


class Written(NamedTuple):
    """What one :func:`dump` put into ``model.pkl``."""

    #: the file's size
    bytes: int
    #: the part of it that took the helper thread
    bytes_hashed_beside_write: int
    #: the CPU seconds of the helper threads that hashed it, which the
    #: caller's own thread clock does not see: on the ``cpu_clock`` the
    #: caller gave (``time.thread_time``, read on each helper), 0.0
    #: where it gave none
    hash_cpu_seconds: float


class _HashingWriter:
    """The pickler's file: every buffer it is handed goes to the file
    and to the md5 once, so the digest is that of the file's bytes with
    no read-back. A large buffer is hashed on a helper thread while this
    one writes it; the helper is joined before ``write`` returns, so
    order is kept and the view outlives no call. A helper reads
    ``cpu_clock`` at its two ends where the caller gave one."""

    def __init__(self, file, cpu_clock: Optional[Callable[[], float]] = None):
        self._file = file
        self._cpu_clock = cpu_clock
        self.digest = hashlib.md5()
        self.bytes = 0
        self.bytes_hashed_beside_write = 0
        self.hash_cpu_seconds = 0.0

    def write(self, data) -> int:
        size = memoryview(data).nbytes
        if size < HASH_BESIDE_WRITE_MIN_BYTES:
            self.digest.update(data)
            self._file.write(data)
        else:
            failed = []

            def hash_it():
                began = self._cpu_clock() if self._cpu_clock else None
                try:
                    self.digest.update(data)
                except BaseException as exc:  # re-raised below, on the caller
                    failed.append(exc)
                if began is not None:
                    # joined before the next helper starts: no two add at once
                    self.hash_cpu_seconds += self._cpu_clock() - began

            helper = threading.Thread(target=hash_it, name="model-pkl-md5")
            helper.start()
            try:
                self._file.write(data)
            finally:
                helper.join()
            if failed:
                raise failed[0]
            self.bytes_hashed_beside_write += size
        self.bytes += size
        return size


def dump(
    obj,
    dest_dir: str,
    metadata: Optional[dict] = None,
    info: Optional[dict] = None,
    cpu_clock: Optional[Callable[[], float]] = None,
) -> Written:
    """
    Serialize ``obj`` into ``dest_dir`` as ``model.pkl`` (+ optional
    ``metadata.json`` / ``info.json``; info always records the model
    checksum: the md5 of ``model.pkl``'s bytes, computed while they are
    written). ``cpu_clock``: a thread's CPU clock for
    ``Written.hash_cpu_seconds``, from a caller that records it.
    """
    os.makedirs(dest_dir, exist_ok=True)
    with open(path.join(dest_dir, MODEL_FILE), "wb") as f:
        writer = _HashingWriter(f, cpu_clock)
        pickle.dump(obj, writer, protocol=MODEL_PICKLE_PROTOCOL)
    if metadata is not None:
        with open(path.join(dest_dir, METADATA_FILE), "w") as f:
            simplejson.dump(metadata, f, default=str, ignore_nan=True)
    full_info = {"checksum": writer.digest.hexdigest()}
    if info:
        full_info.update(info)
    with open(path.join(dest_dir, INFO_FILE), "w") as f:
        simplejson.dump(full_info, f, default=str)
    return Written(
        writer.bytes, writer.bytes_hashed_beside_write, writer.hash_cpu_seconds
    )


TMP_DIR_MARKER = ".tmp-"

#: the fleet builder's crash-safe journal, written beside the artifacts
#: (parallel/journal.py owns its format; the names live here so every
#: artifact-discovery path shares one notion of "not a model")
BUILD_JOURNAL_FILE = "build_state.json"
#: append-only per-machine event overlay (one JSON line per status
#: event), compacted into the base journal at phase boundaries
BUILD_JOURNAL_EVENTS_FILE = "." + BUILD_JOURNAL_FILE + ".events"
#: BUILD_STATUS_FILE / BUILD_TRACE_FILE — the build-progress heartbeat
#: and JSONL span trace written beside the artifacts — are re-exported
#: in the imports above: telemetry/progress.py owns the names and
#: formats (that package must stay stdlib-only importable from the
#: training hot path, so the dependency arrow points this way)


def is_staging_dir(name: str) -> bool:
    """True for atomic-write staging entries (``.<name>.tmp-*`` dirs and
    the journal's ``.build_state.json.tmp-*`` flush files): every
    artifact-discovery path (serving store, model listings, resume) must
    skip them — they are by construction possibly half-written."""
    return name.startswith(".") and TMP_DIR_MARKER in name


def _is_worker_sink(name: str, base: str) -> bool:
    """Per-worker variants of one telemetry sink, rotated generations
    included (``serve_trace-<pid>.jsonl[.N]``, ``fleet_health-<pid>
    .json``); the suffix grammar itself lives in ONE place
    (``telemetry.aggregate.is_worker_variant``)."""
    return is_worker_variant(re.sub(r"\.\d+$", "", name), base)


def is_builder_dropping(name: str) -> bool:
    """True for any non-model entry the fleet builder (or a serving /
    SLO process pointed at the artifact volume) may leave in an
    artifact directory: the build journal, its event overlay, the
    telemetry heartbeat/trace/health-ledger files — including their
    size-rotated generations (``build_trace.jsonl.1`` ...) and the
    per-worker ``-<pid>`` sink variants — the SLO engine's ``rollups/``
    directory, alert-state file and a deployment's ``slos.toml``, and
    atomic-write staging leftovers. Revision cleanup treats a directory
    holding only these as empty; model listings never surface them."""
    return (
        name == BUILD_JOURNAL_FILE
        or name == BUILD_JOURNAL_EVENTS_FILE
        or name == BUILD_STATUS_FILE
        or name == BUILD_TRACE_FILE
        or name == SERVE_TRACE_FILE
        or name == FLEET_HEALTH_FILE
        or name == FLEET_HEALTH_SHARD_DIR
        or name == ROLLUP_DIR
        or name == SLO_STATE_FILE
        or name == SLO_CONFIG_FILE
        or name.startswith(BUILD_TRACE_FILE + ".")
        or name.startswith(SERVE_TRACE_FILE + ".")
        or _is_worker_sink(name, SERVE_TRACE_FILE)
        or _is_worker_sink(name, FLEET_HEALTH_FILE)
        # the sharded health-ledger layout (`fleet_health.d/`,
        # per-worker `fleet_health-<pid>.d/`) is a dropping DIRECTORY
        or _is_worker_sink(name, FLEET_HEALTH_SHARD_DIR)
        or is_staging_dir(name)
    )


def list_model_dirs(directory: str) -> list:
    """Names of the artifact (model) directories under ``directory`` —
    the one shared definition of "what counts as a model entry" for the
    serving store, the model-list route, and resume: directories only,
    builder droppings and dot-entries excluded. Missing directory → []."""
    try:
        entries = os.listdir(directory)
    except FileNotFoundError:
        return []
    return sorted(
        entry
        for entry in entries
        if not entry.startswith(".")
        and not is_builder_dropping(entry)
        and path.isdir(path.join(directory, entry))
    )


#: files an artifact dir may contain; a dest dir holding ONLY these (or
#: nothing) is a prior artifact and safe to swap wholesale
_ARTIFACT_FILES = frozenset({MODEL_FILE, METADATA_FILE, INFO_FILE})


def dump_atomic(
    obj,
    dest_dir: str,
    metadata: Optional[dict] = None,
    info: Optional[dict] = None,
    cpu_clock: Optional[Callable[[], float]] = None,
) -> Written:
    """
    Crash-safe :func:`dump`: artifacts are written into a
    ``.<name>.tmp-*`` sibling staging dir and ``os.replace``-renamed
    into place, so ``dest_dir`` either holds a complete artifact set or
    does not exist — a crash mid-write can never leave a half-written
    ``model.pkl`` where the server's fleet store (or a ``--resume``
    pass) would load it.

    A pre-existing ``dest_dir`` that is empty or a prior artifact is
    replaced whole. A dest dir holding OTHER content (e.g. ``gordo
    build config.yaml .`` — the legacy dump merged into it) is never
    deleted: the three artifact files are moved in individually, each
    with its own atomic ``os.replace``.
    """
    dest_dir = path.normpath(dest_dir)
    parent, name = path.dirname(dest_dir), path.basename(dest_dir)
    os.makedirs(parent or ".", exist_ok=True)
    # Plain os.mkdir (NOT tempfile.mkdtemp): mkdtemp forces mode 0700,
    # which the rename would carry onto the artifact dir and lock out a
    # model server running as a different UID; mkdir honors the umask
    # like os.makedirs always did, with no process-global umask probing
    # (os.umask() round trips race across the dump thread pool).
    while True:
        staging = path.join(
            parent or ".", f".{name}{TMP_DIR_MARKER}{os.getpid()}-{uuid.uuid4().hex[:8]}"
        )
        try:
            os.mkdir(staging)
            break
        except FileExistsError:  # pragma: no cover - 2^32 collision
            continue
    try:
        written = dump(
            obj, staging, metadata=metadata, info=info, cpu_clock=cpu_clock
        )
        fault_point("dump_artifact", name)
        if path.isdir(dest_dir) and not set(os.listdir(dest_dir)) <= _ARTIFACT_FILES:
            # Mixed-content dest: move each artifact file in (file-level
            # atomic), leave everything else untouched.
            for entry in os.listdir(staging):
                os.replace(path.join(staging, entry), path.join(dest_dir, entry))
            os.rmdir(staging)
            return written
        if path.isdir(dest_dir):
            # rename(2) cannot replace a non-empty dir; a complete prior
            # artifact (e.g. a re-build into the same output dir) is
            # swapped out the pre-rename instant before the new one lands.
            shutil.rmtree(dest_dir)
        os.replace(staging, dest_dir)
        return written
    except BaseException:
        shutil.rmtree(staging, ignore_errors=True)
        raise


def load(source_dir: str) -> Any:
    """Load the model saved in ``source_dir`` by ``dump``."""
    model_path = path.join(source_dir, MODEL_FILE)
    with open(model_path, "rb") as f:
        return pickle.load(f)


def _load_json_file(source_dir: str, filename: str) -> dict:
    """
    Load a JSON artifact, falling back to the parent directory — the
    reference stores metadata either beside or one level above the model dir
    (gordo/serializer/serializer.py:77-84).
    """
    for candidate_dir in (source_dir, path.dirname(path.normpath(source_dir))):
        candidate = path.join(candidate_dir, filename)
        if path.isfile(candidate):
            with open(candidate) as f:
                return simplejson.load(f)
    raise FileNotFoundError(
        f"{filename} not found in {source_dir} or its parent directory"
    )


def load_metadata(source_dir: str) -> dict:
    """Load ``metadata.json`` for a model directory."""
    return _load_json_file(source_dir, METADATA_FILE)


def load_info(source_dir: str) -> dict:
    """Load ``info.json`` for a model directory."""
    return _load_json_file(source_dir, INFO_FILE)
