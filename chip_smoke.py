#!/usr/bin/env python3
"""
The quickest proof that gordo-tpu still starts on the chip.

``python chip_smoke.py`` drives the two things a user pays chip time for,
once, through the entry points a user would call, in ONE process (a chip
belongs to one process at a time):

1. **Trainer** — the ``build-fleet`` command over a generated machines
   document: 64 machines of the reference production model
   (``DiffBasedAnomalyDetector`` over ``Pipeline[MinMaxScaler,
   JaxAutoEncoder(kind=feedforward_hourglass)]``, 20 tags, 10 days of
   10-minute ``RandomDataset`` rows, 3-fold CV plus final fit, 5 epochs,
   batch 64 — two of them with ``elu``/``selu`` activations, which the
   serving kernel spells differently) and 4 machines of
   ``JaxLSTMAutoEncoder`` at the production geometry (50 tags, lookback
   60, dims (256,128,64)x2), 1 epoch. Weights are random, from seeds.
2. **Server** — the ``run-server`` command over that output directory on
   a real socket, default knobs first and then with ``--batching``,
   answering ``/prediction`` and ``/anomaly/prediction`` for dense and
   LSTM machines (JSON and Arrow-IPC bodies) and ``prediction/fleet`` for
   every dense machine. Each served reconstruction is compared with the
   plain float32 ``models/nn.py`` forward under
   ``jax.default_matmul_precision("highest")``.
3. **Several chips** — where JAX finds more than one device: the sharded
   == one-device parity of a dense and a windowed bucket on
   ``(n, 1)`` and ``(n/2, 2)`` meshes, and one windowed predict long
   enough to route itself through the ``shard_map``/``ppermute`` ring.

It exits non-zero, and prints no result line, unless JAX reports a TPU
and every check of every phase held; there is no switch that lets it
pass without a chip. The phases are functions of :class:`Sizes`, so
``tests/test_chip_smoke.py`` runs the same code tiny on the CPU. Wall
times printed here are set-up and compile times, never a speed.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import contextlib
import functools
import json
import logging
import math
import os
import shutil
import signal
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
#: everything the smoke writes lands here (listed in .gitignore)
SMOKE_DIR = os.path.join(HERE, "chip_smoke_out")
PROJECT = "chip-smoke"
#: the served collection directory's basename is its revision
REVISION = "1700000000000"

#: largest |served - reference| accepted, as a fraction of
#: max(1, max |reference|), against the float32 ``models/nn.py`` forward
#: at "highest" matmul precision — by platform.
#:
#: On a TPU every serving program multiplies at the chip's default
#: matmul precision, which rounds float32 operands to bf16 (8 mantissa
#: bits) before each product: XLA's programs do, and so does the Pallas
#: kernel — ``preferred_element_type=float32`` only sets the accumulator.
#: Measured on a v5e (PR 22 probe): kernel and XLA-default forward sit
#: at the SAME distance from the reference, 0.3%-1.3% of the output
#: scale for the 7-layer hourglass across all activations, 0.6% for the
#: 6-layer LSTM. 5e-2 passes that rounding and fails a wrong program,
#: whose error is of the order of the output. Training, CV thresholds
#: and serving all run at this same precision. On the CPU every program
#: computes in float32, and 1e-4 holds.
TOLERANCE = {"tpu": 5e-2, "cpu": 1e-4}

#: the transfer rungs' fixed reasons (``ingest/transfer.py``); any other
#: key in ``fallback_reasons`` would be an exception name
TRANSFER_REASONS = {
    "disabled",
    "no_columns",
    "readonly_column",
    "non_contiguous_column",
}


@dataclass(frozen=True)
class Sizes:
    """What the phases run at. The defaults are the command's sizes: the
    full width of the models the repo supports, depth as the issue cut
    it. Tests pass tiny values."""

    dense_machines: int = 64
    dense_tags: int = 20
    dense_epochs: int = 5
    #: activations of the last dense machines, one each, beyond ``tanh``
    dense_variants: Tuple[str, ...] = ("elu", "selu")
    lstm_machines: int = 4
    lstm_tags: int = 50
    lstm_lookback: int = 60
    lstm_dims: Tuple[int, ...] = (256, 128, 64)
    lstm_epochs: int = 1
    batch_size: int = 64
    train_days: int = 10  # of 10-minute RandomDataset rows
    request_rows: int = 200
    arrow_rows: int = 1441  # one past a 512-row kernel block boundary
    fleet_rows: int = 96
    lstm_request_rows: int = 256
    concurrent_clients: int = 4
    ring_rows: int = 65_536  # parallel/sequence.DEFAULT_RING_PREDICT_ROWS

    @property
    def n_machines(self) -> int:
        return self.dense_machines + self.lstm_machines


class Smoke:
    """Collects every failed check of a run, so one chip call reports all
    of them instead of the first."""

    def __init__(self, platform: str) -> None:
        self.tolerance = TOLERANCE[platform]
        self.failures: List[str] = []
        #: program label -> largest |served - reference| seen, absolute
        #: and as a fraction of the reference's scale
        self.max_abs_diff: Dict[str, float] = {}
        self.max_fraction: Dict[str, float] = {}

    def check(self, ok: Any, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"  FAIL: {what}", flush=True)
        return bool(ok)

    def compare(self, program: str, served, reference) -> None:
        """Record ``served`` against ``reference`` under the platform's
        tolerance; shapes must agree and every value be finite."""
        import numpy as np

        served = np.asarray(served, np.float64)
        reference = np.asarray(reference, np.float64)
        if not self.check(
            served.shape == reference.shape,
            f"{program}: served shape {served.shape} != reference "
            f"{reference.shape}",
        ):
            return
        self.check(np.isfinite(served).all(), f"{program}: non-finite values")
        diff = float(np.max(np.abs(served - reference))) if served.size else 0.0
        scale = max(1.0, float(np.max(np.abs(reference)))) if reference.size else 1.0
        self.max_abs_diff[program] = max(diff, self.max_abs_diff.get(program, 0.0))
        self.max_fraction[program] = max(
            diff / scale, self.max_fraction.get(program, 0.0)
        )
        self.check(
            diff <= self.tolerance * scale,
            f"{program}: max |served - float32 reference| {diff:.3e} exceeds "
            f"{self.tolerance:.0e} of the output scale {scale:.2f}",
        )


class _ErrorLog(logging.Handler):
    """Every ERROR-or-worse log record of a phase. The server answers 200
    off a host path when its compiled path refuses, and logs the refusal:
    a run on new hardware that trips that must not look like a pass."""

    def __init__(self) -> None:
        super().__init__(level=logging.ERROR)
        self.records: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(f"{record.name}: {record.getMessage()}")


@contextlib.contextmanager
def _no_error_logs(smoke: Smoke, phase: str):
    handler = _ErrorLog()
    root = logging.getLogger()
    root.addHandler(handler)
    try:
        yield
    finally:
        root.removeHandler(handler)
        for line in handler.records:
            smoke.check(False, f"{phase}: error logged — {line[:300]}")


def device_header() -> Dict[str, Any]:
    """Print where JAX runs, before any work, and return it."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"jax={jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']} device_count={device['count']}",
        flush=True,
    )
    return device


# -- the machines document ----------------------------------------------------


def _model_definition(estimator: str, estimator_kwargs: Dict[str, Any]) -> dict:
    return {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "sklearn.pipeline.Pipeline": {
                    "steps": [
                        "sklearn.preprocessing.MinMaxScaler",
                        {estimator: estimator_kwargs},
                    ]
                }
            }
        }
    }


def machines_document(sizes: Sizes) -> Dict[str, Any]:
    """The ``build-fleet`` input: dense machines (``tanh`` but for one
    machine per ``dense_variants`` activation) and LSTM machines."""
    import datetime

    start = datetime.datetime(2020, 1, 1, tzinfo=datetime.timezone.utc)
    end = start + datetime.timedelta(days=sizes.train_days)

    def dataset(prefix: str, tags: int) -> dict:
        return {
            "type": "RandomDataset",
            "train_start_date": start.isoformat(),
            "train_end_date": end.isoformat(),
            "tag_list": [f"{prefix}-tag-{j:02d}" for j in range(tags)],
        }

    machines = []
    n_tanh = sizes.dense_machines - len(sizes.dense_variants)
    funcs = ["tanh"] * n_tanh + list(sizes.dense_variants)
    for i, func in enumerate(funcs):
        name = f"dense-{i:03d}" if func == "tanh" else f"dense-{func}"
        machines.append(
            {
                "name": name,
                "model": _model_definition(
                    "gordo_tpu.models.JaxAutoEncoder",
                    {
                        "kind": "feedforward_hourglass",
                        "func": func,
                        "epochs": sizes.dense_epochs,
                        "batch_size": sizes.batch_size,
                    },
                ),
                "dataset": dataset(name, sizes.dense_tags),
            }
        )
    dims = list(sizes.lstm_dims)
    for i in range(sizes.lstm_machines):
        name = f"lstm-{i:03d}"
        machines.append(
            {
                "name": name,
                "model": _model_definition(
                    "gordo_tpu.models.JaxLSTMAutoEncoder",
                    {
                        "kind": "lstm_model",
                        "lookback_window": sizes.lstm_lookback,
                        "encoding_dim": dims,
                        "encoding_func": ["tanh"] * len(dims),
                        "decoding_dim": dims[::-1],
                        "decoding_func": ["tanh"] * len(dims),
                        "epochs": sizes.lstm_epochs,
                        "batch_size": sizes.batch_size,
                    },
                ),
                "dataset": dataset(name, sizes.lstm_tags),
            }
        )
    return {"project_name": PROJECT, "machines": machines}


def _names(sizes: Sizes) -> Tuple[List[str], List[str]]:
    doc = machines_document(sizes)
    names = [m["name"] for m in doc["machines"]]
    return names[: sizes.dense_machines], names[sizes.dense_machines :]


# -- phase 1: the trainer -----------------------------------------------------


def _device_peaks() -> Optional[List[int]]:
    """``peak_bytes_in_use`` per local device, or None where the backend
    does not report it (the CPU platform)."""
    import jax

    peaks = []
    for device in jax.local_devices():
        stats = device.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def _run_cli(args: List[str]) -> int:
    """The ``gordo-tpu`` click group in-process, as ``python -m
    gordo_tpu`` enters it; returns the exit code."""
    from gordo_tpu.cli import gordo_tpu_cli

    try:
        gordo_tpu_cli.main(args, standalone_mode=False)
    except SystemExit as exc:
        return int(exc.code or 0)
    return 0


def train_phase(smoke: Smoke, sizes: Sizes, output_dir: str, device: dict) -> None:
    """``build-fleet`` over the generated document into ``output_dir``,
    then everything the artifacts and the build's own status document
    say about how it went."""
    import jax
    import yaml

    print(f"[train] build-fleet: {sizes.n_machines} machines", flush=True)
    config_path = os.path.join(os.path.dirname(output_dir), "machines.yaml")
    with open(config_path, "w") as f:
        yaml.safe_dump(machines_document(sizes), f)

    started = time.monotonic()
    with _no_error_logs(smoke, "train"):
        code = _run_cli(["build-fleet", config_path, output_dir])
    print(
        f"[train] set-up, compile and run wall time {time.monotonic() - started:.1f}s"
        " (not a speed)",
        flush=True,
    )
    if not smoke.check(code == 0, f"build-fleet exited {code}"):
        return

    # a build that contained device faults still exits 0; its status
    # document is where it says so
    with open(os.path.join(output_dir, "build_status.json")) as f:
        status = json.load(f)
    machines = status["machines"]
    robustness = status["robustness"]
    smoke.check(status["state"] == "complete", f"build state {status['state']}")
    for key in ("failed", "degraded", "fallbacks"):
        smoke.check(machines[key] == 0, f"build_status machines.{key}={machines[key]}")
    for key in ("bucket_bisects", "sequential_degraded"):
        smoke.check(
            robustness.get(key, 0) == 0,
            f"build_status robustness.{key}={robustness.get(key)}",
        )
    smoke.check(
        (status.get("device") or {}).get("platform") == device["platform"],
        f"build_status names device {status.get('device')}, JAX {device}",
    )

    artifacts = 0
    dense_names, lstm_names = _names(sizes)
    for name in dense_names + lstm_names:
        if not smoke.check(
            os.path.isfile(os.path.join(output_dir, name, "model.pkl")),
            f"{name}: no model.pkl",
        ):
            continue
        artifacts += 1
        with open(os.path.join(output_dir, name, "metadata.json")) as f:
            build = json.load(f)["metadata"]["build_metadata"]
        meta = build["model"]["model_meta"]
        thresholds = meta.get("feature-thresholds") or []
        smoke.check(
            thresholds
            and all(math.isfinite(t) for t in thresholds)
            and math.isfinite(meta.get("aggregate-threshold") or math.nan),
            f"{name}: thresholds missing or non-finite",
        )
        losses = (meta.get("history") or {}).get("loss") or []
        smoke.check(
            losses and all(math.isfinite(l) for l in losses),
            f"{name}: losses {losses}",
        )
        smoke.check(
            build["model"]["device"].get("platform") == device["platform"],
            f"{name}: metadata names device {build['model']['device']}",
        )
        for key in ("bucket_bisects", "fleet_retries"):
            smoke.check(
                build["robustness"][key] == 0,
                f"{name}: robustness.{key}={build['robustness'][key]}",
            )
    smoke.check(
        artifacts == sizes.n_machines,
        f"{artifacts} artifacts, expected {sizes.n_machines}",
    )
    print(f"[train] {artifacts} artifacts", flush=True)

    # build-fleet spreads every bucket over make_mesh()'s default
    # (models = all devices, data = 1): each device must have held a shard
    peaks = _device_peaks()
    if len(jax.devices()) > 1 and peaks is not None:
        print(f"[train] peak bytes per device: {peaks}", flush=True)
        smoke.check(all(p > 0 for p in peaks), f"idle device(s): peaks {peaks}")


# -- phase 2: the server ------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _http(
    url: str,
    body: Optional[bytes] = None,
    content_type: str = "application/json",
    timeout: float = 600.0,
) -> Tuple[int, bytes]:
    """POST ``body`` (GET without one); returns (status, payload)."""
    request = urllib.request.Request(url, data=body)
    if body is not None:
        request.add_header("Content-Type", content_type)
    try:
        with urllib.request.urlopen(request, timeout=timeout) as response:
            return response.status, response.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


class _Served:
    """One loaded artifact with what a reference needs: its float32
    params, its spec, and the host transformers ahead of the estimator."""

    def __init__(self, output_dir: str, name: str, seed: int, rows: int):
        import numpy as np
        import pandas as pd

        from gordo_tpu import serializer
        from gordo_tpu.server.fleet_store import _find_estimator

        self.name = name
        self.model = serializer.load(os.path.join(output_dir, name))
        self.estimator = _find_estimator(self.model)
        metadata = serializer.load_metadata(os.path.join(output_dir, name))
        dataset = metadata["metadata"]["build_metadata"]["dataset"]["dataset_meta"]
        tags = [t["name"] for t in dataset["tag_list"]]
        hist = dataset["x_hist"]
        # rows drawn inside each tag's training range, from a seed
        rng = np.random.RandomState(seed)
        self.X = pd.DataFrame(
            {
                tag: rng.uniform(hist[tag]["min"], hist[tag]["max"], rows)
                for tag in tags
            },
            index=pd.date_range(
                "2020-02-01", periods=rows, freq="10min", tz="UTC"
            ),
        )

    def reference(self, rows: slice = slice(None)):
        """The plain float32 ``models/nn.py`` forward of ``X[rows]`` at
        the highest matmul precision, on the default device."""
        import jax
        import numpy as np

        from gordo_tpu.models.spec import LSTMSpec
        from gordo_tpu.ops.windows import sliding_windows
        from gordo_tpu.server.fleet_store import _host_transform

        spec = self.estimator.spec_
        X = _host_transform(self.model, self.X[rows])
        if isinstance(spec, LSTMSpec):
            X = sliding_windows(
                X, self.estimator.lookback_window, self.estimator.lookahead
            )
        # the precision context is part of jit's cache key: entered at
        # every call, it selects the program traced under it
        with jax.default_matmul_precision("highest"):
            out = _reference_program(spec)(
                self.estimator.params_, np.asarray(X, np.float32)
            )
        return np.asarray(out)


@functools.lru_cache(maxsize=None)
def _reference_program(spec):
    """One jitted ``models/nn.py`` forward per spec (a bucket's machines
    share it instead of compiling a reference each)."""
    import jax

    from gordo_tpu.models.nn import forward_fn_for

    forward = forward_fn_for(spec)
    return jax.jit(lambda params, x: forward(spec, params, x)[0])


@contextlib.contextmanager
def _recorded_devices(record: List[Tuple[str, Any]]):
    """Observe — never alter — where the serving path's staged inputs and
    program outputs live: the transfer and the fused programs are wrapped
    so each result's ``.devices()`` lands in ``record``."""
    from gordo_tpu import ingest
    from gordo_tpu.serve import engine
    from gordo_tpu.server import fleet_store

    def watching(label: str, fn: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            record.append((label, out.devices()))
            return out

        return wrapper

    originals = [
        (ingest, "to_device"),
        (engine, "to_device"),
        (fleet_store, "fleet_forward"),
        (fleet_store, "fleet_forward_gather"),
    ]
    saved = [(mod, attr, getattr(mod, attr)) for mod, attr in originals]
    for mod, attr, fn in saved:
        setattr(mod, attr, watching(attr, fn))
    try:
        yield
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def _model_output(payload: bytes):
    """The ``model-output`` block of a single-model JSON response as a
    ``[rows, tags]`` array, and whether every number in ``data`` is
    finite."""
    import numpy as np

    from gordo_tpu.server.utils import dataframe_from_dict

    frame = dataframe_from_dict(json.loads(payload)["data"])
    numeric = frame.select_dtypes("number").to_numpy()
    return frame["model-output"].to_numpy(), bool(np.isfinite(numeric).all())


def _requests(
    smoke: Smoke,
    sizes: Sizes,
    output_dir: str,
    base_url: str,
    mode: str,
) -> None:
    """The request matrix of one server run."""
    import numpy as np

    from gordo_tpu.server import wire
    from gordo_tpu.server.utils import dataframe_to_dict

    dense_names, lstm_names = _names(sizes)
    prefix = f"{base_url}/gordo/v0/{PROJECT}"

    def json_body(served: _Served) -> bytes:
        frame = dataframe_to_dict(served.X)
        return json.dumps({"X": frame, "y": frame}).encode()

    def single(served: _Served, body: bytes, content_type: str, label: str):
        reference = served.reference()
        for route in ("prediction", "anomaly/prediction"):
            status, payload = _http(
                f"{prefix}/{served.name}/{route}", body, content_type
            )
            what = f"{mode} {label} {route}"
            if not smoke.check(
                status == 200, f"{what}: HTTP {status} {payload[:200]!r}"
            ):
                continue
            output, finite = _model_output(payload)
            smoke.check(finite, f"{what}: non-finite value in the response")
            smoke.compare(f"{mode}:{label}", output, reference)

    # one dense machine per activation, one LSTM machine, JSON bodies
    variants = [dense_names[0]] + dense_names[
        sizes.dense_machines - len(sizes.dense_variants) :
    ]
    for i, name in enumerate(variants):
        served = _Served(output_dir, name, seed=i, rows=sizes.request_rows)
        single(served, json_body(served), "application/json", f"{name} json")
    lstm = _Served(output_dir, lstm_names[0], seed=50, rows=sizes.lstm_request_rows)
    single(lstm, json_body(lstm), "application/json", "lstm json")

    # the same dense machine with an Arrow-IPC body: the raw-column rung
    arrow = _Served(output_dir, dense_names[0], seed=60, rows=sizes.arrow_rows)
    single(
        arrow,
        wire.encode_request(arrow.X, arrow.X),
        wire.ARROW_CONTENT_TYPE,
        "dense arrow",
    )

    # every dense machine in one fleet request: one fused program a bucket
    fleet = [
        _Served(output_dir, name, seed=100 + i, rows=sizes.fleet_rows)
        for i, name in enumerate(dense_names)
    ]
    body = json.dumps({"X": {s.name: dataframe_to_dict(s.X) for s in fleet}})
    status, payload = _http(f"{prefix}/prediction/fleet", body.encode())
    if smoke.check(status == 200, f"{mode} fleet: HTTP {status} {payload[:200]!r}"):
        doc = json.loads(payload)
        smoke.check(not doc.get("errors"), f"{mode} fleet errors: {doc.get('errors')}")
        for served in fleet:
            entry = doc["data"].get(served.name)
            if not smoke.check(entry, f"{mode} fleet: no entry for {served.name}"):
                continue
            columns = entry["model-output"]
            output = np.column_stack(
                [list(columns[str(c)].values()) for c in range(len(columns))]
            )
            mse = np.asarray(list(entry["total-anomaly-unscaled"].values()))
            smoke.check(
                np.isfinite(mse).all(), f"{mode} fleet {served.name}: non-finite mse"
            )
            smoke.compare(f"{mode}:fleet", output, served.reference())

    # a few clients at once, each on its own dense machine: with
    # --batching these coalesce into gather programs on ladder rungs
    clients = [
        _Served(output_dir, name, seed=200 + i, rows=sizes.request_rows + 7 * i)
        for i, name in enumerate(dense_names[1 : 1 + sizes.concurrent_clients])
    ]
    gate = threading.Barrier(len(clients))
    errors: List[BaseException] = []

    def client(served: _Served) -> None:
        try:
            body = json_body(served)
            gate.wait(timeout=60)
            single(served, body, "application/json", "concurrent json")
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(s,)) for s in clients]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=900)
        smoke.check(not thread.is_alive(), f"{mode}: a concurrent client hung")
    if errors:
        raise errors[0]


def _server_facts(
    smoke: Smoke,
    mode: str,
    batching: bool,
    output_dir: str,
    devices: List[Tuple[str, Any]],
) -> None:
    """What the serving process says about itself after the requests."""
    import jax

    from gordo_tpu import ingest, serve
    from gordo_tpu.server import fleet_store

    platform = jax.default_backend()
    # the kernel is the f32 serving program wherever the platform is a
    # TPU; the smoke asserts what the code selects from, not a constant
    expected = "pallas" if platform == "tpu" else "xla"
    backend = fleet_store.serving_backend()
    smoke.check(backend == expected, f"{mode}: serving_backend() is {backend}")
    programs = fleet_store.program_cache_stats()
    print(f"[{mode}] program cache: {programs}", flush=True)
    smoke.check(
        programs["by_backend"].get(expected, 0) > 0,
        f"{mode}: no compiled {expected} signature in {programs}",
    )
    smoke.check(
        set(programs["by_backend"]) == {expected},
        f"{mode}: dense programs on backends {sorted(programs['by_backend'])}",
    )

    stats = ingest.ingest_stats()
    print(f"[{mode}] ingest: {stats}", flush=True)
    smoke.check(
        set(stats["fallback_reasons"]) <= TRANSFER_REASONS,
        f"{mode}: transfer fallback reasons {stats['fallback_reasons']}",
    )
    if ingest.dlpack_enabled():
        # float64 Arrow columns are cast on the host and cross over dlpack
        smoke.check(
            stats["dlpack_transfers"] > 0, f"{mode}: the dlpack rung never ran"
        )

    smoke.check(devices, f"{mode}: no staged input or program output was observed")
    off_device = sorted(
        {
            f"{label} on {device}"
            for label, placed in devices
            for device in placed
            if device.platform != platform
        }
    )
    smoke.check(not off_device, f"{mode}: arrays off the {platform}: {off_device}")
    fleet = fleet_store.STORE.fleet(output_dir)
    for spec in set(fleet.loaded_specs().values()):
        _, stacked = fleet.spec_bucket(spec)
        placed = {
            d.platform
            for leaf in jax.tree_util.tree_leaves(stacked)
            for d in leaf.devices()
        }
        smoke.check(
            placed == {platform},
            f"{mode}: {type(spec).__name__} bucket lives on {placed}",
        )

    engine = serve.get_engine()
    smoke.check(
        (engine is not None) == batching,
        f"{mode}: engine {'missing' if batching else 'present'}",
    )
    if engine is None:
        return
    engine_stats = engine.stats()
    print(
        f"[{mode}] engine: "
        + json.dumps({k: v for k, v in engine_stats.items() if k != "ingest"}, default=str),
        flush=True,
    )
    smoke.check(engine_stats["warmup_programs"] > 0, f"{mode}: warmup compiled nothing")
    smoke.check(engine_stats["coalesced"] > 0, f"{mode}: no request was batched")
    for key in (
        "precision_degraded",
        "device_errors",
        "batch_bisects",
        "members_isolated",
        "nonfinite_outputs",
        "breaker_trips",
        "rung_demotions",
        "oom_fallbacks",
        "shed_queue_full",
        "shed_deadline",
    ):
        smoke.check(engine_stats[key] == 0, f"{mode}: engine {key}={engine_stats[key]}")
    gates = fleet.precision_reports()
    smoke.check(
        all(g.get("passed") for g in gates), f"{mode}: precision gate failed: {gates}"
    )


def serve_phase(smoke: Smoke, sizes: Sizes, output_dir: str, batching: bool) -> None:
    """``run-server`` over ``output_dir`` on a real socket in this
    process — the server in the main thread, where its SIGTERM handler
    lives; the clients in another, which ends the run the way an operator
    would: with SIGTERM, through the server's graceful drain."""
    from gordo_tpu import ingest, serve
    from gordo_tpu.server import fleet_store

    mode = "batching" if batching else "default"
    port = _free_port()
    base_url = f"http://127.0.0.1:{port}"
    print(f"[serve:{mode}] run-server on {base_url}", flush=True)
    devices: List[Tuple[str, Any]] = []
    failure: List[BaseException] = []
    server_exited = threading.Event()

    def clients() -> None:
        try:
            deadline = time.monotonic() + 300
            while True:
                try:
                    if _http(f"{base_url}/healthcheck", timeout=5)[0] == 200:
                        break
                except OSError:
                    pass
                if server_exited.is_set() or time.monotonic() > deadline:
                    raise RuntimeError("the server did not come up")
                time.sleep(0.2)
            if batching:
                # warmup compiles the ladder in a background thread; the
                # check on its counter needs it finished
                for thread in threading.enumerate():
                    if thread.name == "gordo-serve-warmup":
                        thread.join(timeout=900)
                        smoke.check(not thread.is_alive(), "warmup still running")
            started = time.monotonic()
            _requests(smoke, sizes, output_dir, base_url, mode)
            print(
                f"[serve:{mode}] requests incl. first-call compiles "
                f"{time.monotonic() - started:.1f}s (not a speed)",
                flush=True,
            )
            _server_facts(smoke, mode, batching, output_dir, devices)
        except BaseException as exc:  # noqa: BLE001 - re-raised in main thread
            failure.append(exc)
        finally:
            if not server_exited.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    saved_env = dict(os.environ)
    saved_handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    # until the server installs its own handler, SIGTERM must not end
    # this process (a server that died at start-up never installs one)
    signal.signal(signal.SIGTERM, lambda signum, frame: None)
    os.environ["MODEL_COLLECTION_DIR"] = output_dir
    ingest.reset_ingest_stats()
    thread = threading.Thread(target=clients, name="smoke-clients")
    args = ["run-server", "--host", "127.0.0.1", "--port", str(port), "--log-level", "info"]
    try:
        with _no_error_logs(smoke, f"serve:{mode}"), _recorded_devices(devices):
            thread.start()
            try:
                code = _run_cli(args + (["--batching"] if batching else []))
            finally:
                server_exited.set()
                thread.join(timeout=60)
        smoke.check(code == 0, f"run-server ({mode}) exited {code}")
        smoke.check(not thread.is_alive(), f"{mode}: client thread still running")
    finally:
        for signum, handler in saved_handlers.items():
            signal.signal(signum, handler)
        os.environ.clear()
        os.environ.update(saved_env)
        # the drain shut the engine's threads down; drop the engine and
        # the resident revision so the next run starts like a new process
        serve.reset_engine(drain=False)
        fleet_store.STORE.clear()
    if failure:
        raise failure[0]


# -- phase 3: several chips ---------------------------------------------------


def sharded_parity(
    devices, data_parallelism: int, rtol: float = 1e-5, atol: float = 1e-7
) -> Dict[str, float]:
    """
    One fused fleet training program on a ``(len(devices) /
    data_parallelism, data_parallelism)`` mesh against the same members
    on a one-device mesh, for a dense and a windowed (LSTM) bucket: the
    per-member losses and params must agree — the device-count
    independence of the per-member RNG design (parallel/fleet.py) and of
    the on-device window gather. Raises on a mismatch; returns the
    largest differences seen. The float32 tolerance holds on real chips
    as on virtual CPU devices: four v5e chips agreed with one to 1e-9
    on both meshes (PERF.md, PR 22).
    """
    import jax
    import numpy as np

    from gordo_tpu.models.factories import feedforward_hourglass, lstm_model
    from gordo_tpu.models.training import FitConfig
    from gordo_tpu.ops.windows import window_targets
    from gordo_tpu.parallel import (
        FleetMember,
        FleetTrainer,
        WindowedFleetMember,
        make_mesh,
    )

    mesh = make_mesh(devices=devices, data_parallelism=data_parallelism)
    model_axis = len(devices) // data_parallelism
    spec = feedforward_hourglass(8, encoding_layers=2)
    lstm_spec = lstm_model(
        4, lookback_window=6, encoding_dim=(8,), encoding_func=("tanh",),
        decoding_dim=(8,), decoding_func=("tanh",),
    )

    # More members than model-axis shards exercises both sharding and
    # within-shard batching; ragged lengths exercise the mask machinery.
    def dense_members():
        rng = np.random.RandomState(0)
        return [
            FleetMember(
                name=f"m{i}",
                spec=spec,
                X=(X := rng.rand(24 + 8 * (i % 2), 8).astype(np.float32)),
                y=X.copy(),
                seed=i,
            )
            for i in range(2 * model_axis)
        ]

    def lstm_members():
        rng = np.random.RandomState(1)
        return [
            WindowedFleetMember(
                name=f"w{i}",
                spec=lstm_spec,
                series=(S := rng.rand(40, 4).astype(np.float32)),
                targets=window_targets(S, 6, 0),
                seed=i,
            )
            for i in range(model_axis)
        ]

    config = FitConfig(epochs=1, batch_size=8, validation_split=0.25, shuffle=True)
    lstm_config = FitConfig(epochs=1, batch_size=8, shuffle=False)
    single = make_mesh(devices=devices[:1], data_parallelism=1)
    worst = {"loss": 0.0, "param": 0.0}
    for kind, members, fit in (
        ("dense", dense_members, config),
        ("windowed", lstm_members, lstm_config),
    ):
        sharded = FleetTrainer(mesh=mesh).train(members(), fit)
        unsharded = FleetTrainer(mesh=single).train(members(), fit)
        for s, u in zip(sharded, unsharded):
            pairs = [("loss", s.history.history["loss"], u.history.history["loss"])]
            pairs += [
                ("param", ls, lu)
                for ls, lu in zip(
                    jax.tree_util.tree_leaves(s.params),
                    jax.tree_util.tree_leaves(u.params),
                )
            ]
            for what, a, b in pairs:
                a, b = np.asarray(a), np.asarray(b)
                if not np.isfinite(a).all():
                    raise RuntimeError(f"{kind} {s.name}: non-finite {what}")
                worst[what] = max(worst[what], float(np.max(np.abs(a - b))))
                if not np.allclose(a, b, rtol=rtol, atol=atol):
                    raise RuntimeError(
                        f"{kind} {s.name}: sharded {what} differs from the "
                        f"one-device run on mesh {dict(mesh.shape)} (max "
                        f"delta {np.max(np.abs(a - b)):.3e})"
                    )
    return worst


def multichip_phase(smoke: Smoke, sizes: Sizes, output_dir: str) -> None:
    """What only a host with several devices can run. With one device it
    prints that and returns."""
    import jax
    import numpy as np

    from gordo_tpu.parallel import sequence

    devices = jax.devices()
    if len(devices) == 1:
        print("[multichip] one device: nothing to shard", flush=True)
        return
    layouts = [1] + ([2] if len(devices) % 2 == 0 else [])
    for data_parallelism in layouts:
        shape = (len(devices) // data_parallelism, data_parallelism)
        try:
            worst = sharded_parity(devices, data_parallelism)
            print(f"[multichip] mesh {shape}: sharded == one device, {worst}", flush=True)
        except RuntimeError as exc:
            smoke.check(False, f"multichip mesh {shape}: {exc}")

    # a windowed predict long enough to route itself through the ring
    _, lstm_names = _names(sizes)
    served = _Served(output_dir, lstm_names[0], seed=300, rows=sizes.ring_rows)
    smoke.check(
        sequence.ring_predict_enabled(sizes.ring_rows),
        f"{sizes.ring_rows} rows on {len(devices)} devices do not take the ring",
    )
    ring_programs = sequence._ring_program.cache_info().currsize
    started = time.monotonic()
    output = np.asarray(served.model.predict(served.X))
    print(
        f"[multichip] ring predict of {sizes.ring_rows} rows: compile and run "
        f"{time.monotonic() - started:.1f}s (not a speed)",
        flush=True,
    )
    smoke.check(
        sequence._ring_program.cache_info().currsize == ring_programs + 1,
        "the ring program was not built",
    )
    # against the reference on the head and the tail of the series (the
    # tail crosses the last device's halo)
    lookback = served.estimator.lookback_window
    span = min(1024, len(output))
    head = slice(0, span + lookback - 1)
    tail = slice(sizes.ring_rows - span - lookback + 1, sizes.ring_rows)
    smoke.compare("multichip:ring head", output[:span], served.reference(head))
    smoke.compare("multichip:ring tail", output[-span:], served.reference(tail))


# -- the command --------------------------------------------------------------


def run_phases(smoke: Smoke, sizes: Sizes, smoke_dir: str, device: dict) -> None:
    """Every phase, in order, into ``smoke_dir`` (which this call owns)."""
    from gordo_tpu.parallel.mesh import configure_compile_cache
    from gordo_tpu.telemetry import utilization_snapshot

    shutil.rmtree(smoke_dir, ignore_errors=True)
    output_dir = os.path.join(smoke_dir, REVISION)
    os.makedirs(smoke_dir)
    print(f"persistent compile cache: {configure_compile_cache() or 'none'}", flush=True)
    train_phase(smoke, sizes, output_dir, device)
    if not smoke.failures:
        serve_phase(smoke, sizes, output_dir, batching=False)
        serve_phase(smoke, sizes, output_dir, batching=True)
        multichip_phase(smoke, sizes, output_dir)
    print(
        "persistent compile cache after the run: "
        f"{utilization_snapshot().get('persistent_cache')}",
        flush=True,
    )


def main() -> int:
    if sys.argv[1:]:
        print("usage: python chip_smoke.py  (no arguments)", file=sys.stderr)
        return 2
    # the product's own log lines (where each entry point says it runs,
    # what the build contained) belong in the smoke's record
    logging.basicConfig(
        level=logging.INFO, format="%(levelname)s [%(name)s] %(message)s"
    )
    device = device_header()
    if device["platform"] != "tpu":
        print(
            f"chip_smoke needs a TPU; JAX reports platform={device['platform']}",
            file=sys.stderr,
        )
        return 1
    smoke = Smoke(device["platform"])
    started = time.monotonic()
    run_phases(smoke, Sizes(), SMOKE_DIR, device)
    print(f"whole run {time.monotonic() - started:.1f}s, set-up and compile included")
    print(
        "largest |served - float32 reference| per program, absolute and as a "
        f"fraction of the output scale (tolerance {smoke.tolerance:.0e}): "
        + json.dumps(
            {
                program: [float(f"{diff:.3e}"), float(f"{smoke.max_fraction[program]:.3e}")]
                for program, diff in sorted(smoke.max_abs_diff.items())
            }
        )
    )
    if smoke.failures:
        print(f"{len(smoke.failures)} check(s) failed:", file=sys.stderr)
        for failure in smoke.failures:
            print(f"  - {failure}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
