"""
Multichip dryrun invariant as a pytest guard: a fresh process forced
onto 8 virtual host devices (``--xla_force_host_platform_device_count=8``,
the CI stand-in for an 8-chip slice) must train a sharded fleet to the
SAME params and losses as a 1-device mesh of the same process.

The in-process suite (tests/parallel/test_fleet.py) covers this under
the conftest's virtual mesh; this subprocess variant pins the XLA flag
explicitly so the sharded == one-device invariant stays guarded even if
the conftest bootstrap changes. On real chips the same comparison is
part of ``python chip_smoke.py`` wherever it finds more than one device.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.planner

SCRIPT = textwrap.dedent(
    """
    import json
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from gordo_tpu.models.factories import feedforward_symmetric
    from gordo_tpu.models.training import FitConfig
    from gordo_tpu.parallel import FleetMember, FleetTrainer, make_mesh

    assert len(jax.devices()) == 8, jax.devices()

    spec = feedforward_symmetric(3, dims=(6, 3), funcs=("tanh", "tanh"))
    config = FitConfig(epochs=2, batch_size=16, shuffle=False)

    def members():
        out = []
        for i in range(4):
            rng = np.random.RandomState(i)
            X = rng.rand(64, 3).astype(np.float32)
            out.append(
                FleetMember(name=f"m{i}", spec=spec, X=X, y=X.copy(), seed=i)
            )
        return out

    sharded_mesh = make_mesh()
    assert sharded_mesh.devices.shape == (8, 1)
    sharded = FleetTrainer(mesh=sharded_mesh).train(members(), config)
    single = FleetTrainer(mesh=make_mesh(jax.devices()[:1])).train(
        members(), config
    )

    max_param_delta = 0.0
    max_loss_delta = 0.0
    for a, b in zip(sharded, single):
        for la, lb in zip(
            jax.tree_util.tree_leaves(a.params),
            jax.tree_util.tree_leaves(b.params),
        ):
            max_param_delta = max(
                max_param_delta, float(np.abs(np.asarray(la) - np.asarray(lb)).max())
            )
        max_loss_delta = max(
            max_loss_delta,
            float(
                np.abs(
                    np.asarray(a.history.history["loss"])
                    - np.asarray(b.history.history["loss"])
                ).max()
            ),
        )
    print(
        "MULTICHIP_RESULT "
        + json.dumps(
            {
                "n_devices": len(jax.devices()),
                "mesh": list(sharded_mesh.devices.shape),
                "models": len(sharded),
                "max_param_delta": max_param_delta,
                "max_loss_delta": max_loss_delta,
            }
        )
    )
    """
)


def test_sharded_build_matches_single_device_in_forced_8_device_process():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(
        l for l in proc.stdout.splitlines() if l.startswith("MULTICHIP_RESULT ")
    )
    result = json.loads(line.split(" ", 1)[1])
    assert result["n_devices"] == 8
    assert result["mesh"] == [8, 1]
    assert result["models"] == 4
    # float32 pipeline: sharded placement must not change the math
    assert result["max_param_delta"] < 5e-5
    assert result["max_loss_delta"] < 5e-5
