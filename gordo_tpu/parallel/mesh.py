"""
Device-mesh construction for fleet training.

The framework's scale axis is the *model fleet* (SURVEY.md §2.9: the
reference fans one k8s pod out per machine; we fan the same fleet across
TPU chips). The canonical mesh is 2D:

- ``models`` — embarrassingly parallel axis: each chip group trains a
  disjoint shard of the stacked model batch (no collectives needed).
- ``data`` — optional second axis sharding each model's sample dimension;
  GSPMD inserts the gradient reductions (psum over ``data``) that the
  reference had no analog for (it had no in-process distributed training
  at all).

Multi-host: `jax.distributed.initialize()` (see ``initialize_backend``)
makes ``jax.devices()`` span the slice; the same mesh code then shards over
ICI/DCN without change.
"""

import logging
import os

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

logger = logging.getLogger(__name__)

MODEL_AXIS = "models"
DATA_AXIS = "data"

#: JAX's own variable for the persistent compilation cache directory.
#: Where it is set JAX reads it itself and this package sets no
#: directory in code, so whoever runs the program decides where compiled
#: programs persist.
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """``<checkout>/.jax_cache``, computed from the package location: the
    directory is part of a cache entry's key, so it must be the same
    path in every process and every run."""
    package_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_root), ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """
    The one place the persistent compilation cache is configured — every
    entry point that compiles (``make_mesh``, the ``build`` command,
    ``server.build_app``, ``bench.py``, ``chip_smoke.py``) calls it
    before its first program. Returns the directory in use, or None.

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself;
      no directory is set in code.
    - unset, on an accelerator: ``<checkout>/.jax_cache``.
    - unset, on the ``cpu`` platform: no directory. XLA:CPU entries
      embed the compile host's machine features and are refused (or
      worse) on another host, and the test suite must not grow a cache
      inside the checkout.

    The min-compile-time and min-entry-size thresholds are zeroed either
    way: fleet programs are many small autoencoders, and JAX's 1 s
    default would skip exactly the programs a heterogeneous fleet
    recompiles most often. Calling this initialises the JAX backend
    (it asks for the platform), which is why the CLI group does not call
    it for host-only commands such as ``fleet-status``.
    """
    from ..telemetry.device import note_compile_cache_dir, watch_compile_path

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = os.environ.get(JAX_CACHE_DIR_ENV) or None
    if cache_dir is None:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = default_compile_cache_dir()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # device telemetry inventories the cache (entries/bytes on disk and
    # this process's hits/misses) for fleet-status and Prometheus
    note_compile_cache_dir(cache_dir)
    watch_compile_path()
    logger.debug("JAX persistent compilation cache at %s", cache_dir)
    return cache_dir


def announce_device(entry_point: str) -> Optional[str]:
    """Configure the compile cache and log where ``entry_point`` runs
    (platform, device kind and count as JAX reports them) — the first
    thing ``build``, ``build-fleet`` and ``server.build_app`` do, so a
    run JAX quietly started on the CPU is visible in the first log
    line. Returns the compile-cache directory in use."""
    from ..telemetry.device import device_identity

    cache_dir = configure_compile_cache()
    logger.info(
        "%s on %s (persistent compile cache: %s)",
        entry_point,
        device_identity(),
        cache_dir or "none",
    )
    return cache_dir


def initialize_backend(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
):
    """
    Initialize multi-host JAX when running on a multi-host TPU slice; no-op
    for single-process runs. This replaces the reference's "distributed
    backend" row (which was k8s pod fan-out, SURVEY.md §2.9) with XLA
    collectives over ICI/DCN.
    """
    configure_compile_cache()
    if coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data_parallelism: int = 1,
    axis_names: Tuple[str, str] = (MODEL_AXIS, DATA_AXIS),
) -> Mesh:
    """
    Build the fleet mesh over ``devices`` (default: all local devices).

    ``data_parallelism`` chips cooperate per model shard; the rest of the
    device count spreads the model axis.
    """
    configure_compile_cache()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % data_parallelism != 0:
        raise ValueError(
            f"data_parallelism={data_parallelism} does not divide device "
            f"count {n}"
        )
    grid = np.array(devices).reshape(n // data_parallelism, data_parallelism)
    return Mesh(grid, axis_names)


def model_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Sharding for arrays stacked on a leading model axis: [M, ...]."""
    return NamedSharding(
        mesh, PartitionSpec(mesh.axis_names[0], *([None] * extra_dims))
    )


def model_data_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """Sharding for [M, N, ...] arrays: models × sample axis."""
    return NamedSharding(
        mesh,
        PartitionSpec(mesh.axis_names[0], mesh.axis_names[1], *([None] * extra_dims)),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
