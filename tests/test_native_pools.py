"""
The suite runs one thread a native pool (``tests/conftest.py``): the
test process has the three variables, the pools that are loaded obey
them, and a process started by a test inherits them, which is how the
chip harness's children, ``chip_smoke.py --cpu`` and the fetch workers
get the same limit with no file of theirs touched.
"""

import json
import os
import subprocess
import sys

import pytest
import threadpoolctl

NATIVE_POOL_VARIABLES = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS")


def a_developer_asked_otherwise() -> bool:
    return any(os.environ.get(name) != "1" for name in NATIVE_POOL_VARIABLES)


@pytest.mark.parametrize("variable", NATIVE_POOL_VARIABLES)
def test_the_test_process_has_the_variable(variable):
    """At 1, or at what the developer set: never unset."""
    assert int(os.environ[variable]) >= 1


def test_every_loaded_pool_runs_one_thread():
    if a_developer_asked_otherwise():
        pytest.skip("a native-pool variable was set to another value by hand")
    import scipy.linalg  # noqa: F401 - scipy's own OpenBLAS
    import sklearn.cluster  # noqa: F401 - libgomp, through scikit-learn's OpenMP helpers

    pools = threadpoolctl.threadpool_info()
    assert pools, "numpy alone brings one pool"
    assert [pool for pool in pools if pool["num_threads"] != 1] == []


def test_a_process_started_by_a_test_sees_the_variables():
    child = subprocess.run(
        [
            sys.executable,
            "-c",
            "import json, os, sys; json.dump({n: os.environ.get(n) for n in sys.argv[1:]}, sys.stdout)",
            *NATIVE_POOL_VARIABLES,
        ],
        capture_output=True,
        text=True,
        check=True,
    )
    assert json.loads(child.stdout) == {name: os.environ[name] for name in NATIVE_POOL_VARIABLES}
