"""
The one compile-cache rule (``parallel/mesh.configure_compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` decides where it is set, the checkout's
``.jax_cache`` serves accelerators where it is not, and the CPU platform
— this suite — gets no directory, so a test run grows nothing in the
checkout that the chip tool would then copy.
"""

import os
import subprocess
import sys

import jax

from gordo_tpu.parallel import mesh
from gordo_tpu.telemetry import device

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CHECKOUT_CACHE = os.path.join(REPO_ROOT, ".jax_cache")


def test_default_directory_is_in_the_checkout():
    assert mesh.default_compile_cache_dir() == CHECKOUT_CACHE


def test_cpu_platform_sets_no_directory(monkeypatch):
    monkeypatch.delenv(mesh.JAX_CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert mesh.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    # the thresholds are zeroed whatever the directory
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_accelerator_without_the_variable_uses_the_checkout(monkeypatch):
    monkeypatch.delenv(mesh.JAX_CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    existed = os.path.exists(CHECKOUT_CACHE)
    try:
        assert mesh.configure_compile_cache() == CHECKOUT_CACHE
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE
        assert device.persistent_cache_info()["path"] == CHECKOUT_CACHE
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        device.note_compile_cache_dir(None)
    # configuring names the directory; only a compile would create it
    assert os.path.exists(CHECKOUT_CACHE) == existed


def test_variable_set_wins_and_nothing_lands_in_the_checkout(tmp_path):
    """JAX reads the variable itself at import, so this runs in a process
    that starts with it set: the function sets no directory in code."""
    cache_dir = tmp_path / "outside-cache"
    existed = os.path.exists(CHECKOUT_CACHE)
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax\n"
            "from gordo_tpu.parallel.mesh import configure_compile_cache\n"
            "print(configure_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n",
        ],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            mesh.JAX_CACHE_DIR_ENV: str(cache_dir),
            "PYTHONPATH": REPO_ROOT,
        },
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache_dir), str(cache_dir)]
    assert os.path.exists(CHECKOUT_CACHE) == existed
