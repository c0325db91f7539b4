"""The chip benchmark's tests: everything runs on the CPU, tiny. The
benchmark's own directories go on ``sys.path`` as ``run.py`` and the
children put them there."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP_DIR = os.path.join(ROOT, "benchmarks", "chip")
HERE = os.path.dirname(os.path.abspath(__file__))
for _path in (HERE, os.path.join(CHIP_DIR, "procs"), CHIP_DIR, ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)
