"""The benchmark's own tests: its modules live in ``chipbench/`` and
import one another by their top-level names."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIPBENCH = os.path.join(ROOT, "chipbench")
if CHIPBENCH not in sys.path:
    sys.path.insert(0, CHIPBENCH)


@pytest.fixture
def small():
    """Each configuration at a toy size, which a CPU runs in
    milliseconds."""
    return {
        "himeno-M": {"grid": [9, 9, 17], "nn": 2},
        "nasft-A": {"grid": [8, 8, 8], "niter": 2},
    }
