"""The harness's own checks (not tier-1): ``python -m pytest benchmark/tests``
under ``JAX_PLATFORMS=cpu``.  They run the tiny fixture cells of
``benchmark/tests/data`` through the same ``run_cell`` a chip run uses."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DATA = os.path.join(ROOT, "benchmark", "tests", "data")
