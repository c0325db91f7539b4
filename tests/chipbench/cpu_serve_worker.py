"""The serve cell's child on the CPU, for the tests: ``serve_worker``'s
own code behind a stand-in for the chip (the real entry refuses to start
without one)."""

import json
import logging
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import conftest  # noqa: E402,F401 - puts the benchmark on sys.path

import common  # noqa: E402
import serve_worker  # noqa: E402
from tiny import CPU_DEVICE, quiet_start  # noqa: E402

if __name__ == "__main__":
    common.die_with_parent()
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    logging.basicConfig(
        level=logging.INFO, filename=os.path.join(spec["run_dir"], "child.log")
    )
    counter, errors = quiet_start()
    sys.exit(serve_worker.serve(spec, dict(CPU_DEVICE), counter, errors))
