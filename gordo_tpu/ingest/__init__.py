"""
Device-resident ingest: compiled preprocessing plans and raw-column
device transfer.

The subsystem has two halves. :mod:`gordo_tpu.ingest.plan` turns each
served artifact's sklearn scaler pipeline into a composed affine plan
and stacks a spec bucket's plans into device-resident
``[members, features]`` arrays, so preprocessing runs as a fused
prologue inside the gather program instead of as per-request host numpy.
:mod:`gordo_tpu.ingest.transfer` carries decoded wire columns
(:class:`~gordo_tpu.ingest.transfer.RawColumns`) to the serving device
over dlpack without the legacy ``column_stack`` staging copy; columns
dlpack cannot export take the host staging rung, chosen by inspecting
them.

Layering: this package sits beside ``planner``/``parallel`` — it may be
imported by ``server``/``serve``/``stream`` but never imports them (the
``gordo_tpu/ingest`` arrow in ``analysis/contracts.toml``).

Both halves are independently switchable:

- ``GORDO_TPU_INGEST_COMPILED`` (default on) — compiled plans + fused
  preprocessing prologue; off = every request takes the host sklearn
  walk, exactly the pre-ingest serving path.
- ``GORDO_TPU_INGEST_DLPACK`` (default on) — per-column dlpack device
  transfer; off = host ``column_stack`` staging (the transfer fallback
  rung) while compiled plans stay active. The dlpack rung only engages
  on accelerator backends: on CPU both rungs stage through host memory,
  so the per-column device dispatch is pure overhead and host staging
  IS the fast rung.
"""

from gordo_tpu.ingest.plan import (  # noqa: F401
    FleetIngestPlan,
    MemberPlan,
    build_fleet_plan,
    extract_member_plan,
)
from gordo_tpu.ingest.transfer import (  # noqa: F401
    RawColumns,
    ingest_stats,
    reset_ingest_stats,
    to_device,
)
from gordo_tpu.utils.env import env_bool

INGEST_COMPILED_ENV = "GORDO_TPU_INGEST_COMPILED"
INGEST_DLPACK_ENV = "GORDO_TPU_INGEST_DLPACK"


def compiled_enabled() -> bool:
    """Whether serving should compile preprocessing into the fused
    gather program (re-read per request so operators can flip it live)."""
    return env_bool(INGEST_COMPILED_ENV, True)


def dlpack_enabled() -> bool:
    """Whether serving's device transfer should try the per-column
    dlpack rung before the host staging rung: the env knob is the
    operator kill-switch, and on the CPU backend the rung never engages
    (both rungs stage through host memory there, so the per-column
    device dispatch is pure overhead). Explicit
    ``to_device(..., dlpack=True)`` callers still get the rung on any
    backend."""
    import jax

    return env_bool(INGEST_DLPACK_ENV, True) and jax.default_backend() != "cpu"
