"""
Real multi-process ``jax.distributed`` execution: two local CPU processes
join a coordinator, run the CLI ``build-fleet`` path through
``_maybe_init_distributed`` (cli/cli.py) over the global 2-device mesh,
and only the coordinator writes artifacts — which must match a
single-process build of the same config.

This is the in-CI stand-in for a 2-host TPU slice: same
coordinator/process-id wiring the workflow template injects
(JAX_COORDINATOR_ADDRESS / JAX_PROCESS_COUNT / JAX_PROCESS_INDEX), same
SPMD program, ICI/DCN collectives replaced by the CPU backend's transport.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

CONFIG = """
project_name: dist-test
machines:
  - name: dist-machine-a
    project_name: dist-test
    model:
      gordo_tpu.models.JaxAutoEncoder:
        kind: feedforward_hourglass
        encoding_layers: 1
        epochs: 2
    dataset:
      type: RandomDataset
      train_start_date: "2020-01-01T00:00:00+00:00"
      train_end_date: "2020-01-02T00:00:00+00:00"
      tag_list: [dist-tag-1, dist-tag-2]
  - name: dist-machine-b
    project_name: dist-test
    model:
      gordo_tpu.models.JaxAutoEncoder:
        kind: feedforward_hourglass
        encoding_layers: 1
        epochs: 2
    dataset:
      type: RandomDataset
      train_start_date: "2020-01-01T00:00:00+00:00"
      train_end_date: "2020-01-02T00:00:00+00:00"
      tag_list: [dist-tag-3, dist-tag-4]
"""

# Worker: pin the CPU backend *before* any JAX backend initializes (two
# workers must not contend for one chip), then run the real CLI command
# in-process so _maybe_init_distributed handles the coordinator
# handshake exactly as a fleet-builder pod would.
WORKER = textwrap.dedent(
    """
    import sys

    import jax

    jax.config.update("jax_platforms", "cpu")

    from click.testing import CliRunner

    from gordo_tpu.cli.cli import build_fleet

    config_path, output_dir = sys.argv[1], sys.argv[2]
    result = CliRunner().invoke(
        build_fleet, [config_path, output_dir], catch_exceptions=False
    )
    print(result.output)
    sys.exit(result.exit_code)
    """
)


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _run_fleet_processes(tmp_path, config_path, n_processes=2, timeout=420):
    port = _free_port()
    out_dirs = []
    procs = []
    logs = []
    for rank in range(n_processes):
        out_dir = tmp_path / f"out-rank{rank}"
        out_dirs.append(out_dir)
        env = {
            **os.environ,
            "JAX_PROCESS_COUNT": str(n_processes),
            "JAX_PROCESS_INDEX": str(rank),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            # the conftest's 8-device flag would give 16 global devices;
            # keep it simple: one CPU device per process
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        }
        log = open(tmp_path / f"rank{rank}.log", "w")
        logs.append(log)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER, str(config_path), str(out_dir)],
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )
        )
    codes = [proc.wait(timeout=timeout) for proc in procs]
    for log in logs:
        log.close()
    if any(codes):
        for rank in range(n_processes):
            print(f"--- rank {rank} log ---")
            print((tmp_path / f"rank{rank}.log").read_text()[-3000:])
    return codes, out_dirs


def test_two_process_build_fleet_matches_single_process(tmp_path):
    config_path = tmp_path / "machines.yaml"
    config_path.write_text(CONFIG)

    codes, out_dirs = _run_fleet_processes(tmp_path, config_path)
    assert codes == [0, 0]

    # Only the coordinator (process 0) writes artifacts.
    assert (out_dirs[0] / "dist-machine-a" / "model.pkl").exists()
    assert (out_dirs[0] / "dist-machine-b" / "model.pkl").exists()
    assert not out_dirs[1].exists()

    # Single-process ground truth, same config.
    from click.testing import CliRunner

    from gordo_tpu.cli.cli import build_fleet

    single_dir = tmp_path / "single"
    result = CliRunner().invoke(
        build_fleet, [str(config_path), str(single_dir)], catch_exceptions=False
    )
    assert result.exit_code == 0

    # The distributed run must produce the same models: compare predictions
    # on a fixed probe (training is seeded; the model axis shards across
    # processes without changing any per-model math).
    from gordo_tpu import serializer

    probe = np.random.RandomState(0).rand(16, 2).astype(np.float32)
    for name in ("dist-machine-a", "dist-machine-b"):
        dist_model = serializer.load(str(out_dirs[0] / name))
        single_model = serializer.load(str(single_dir / name))
        np.testing.assert_allclose(
            dist_model.predict(probe),
            single_model.predict(probe),
            rtol=1e-5,
            atol=1e-6,
        )
