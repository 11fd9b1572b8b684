"""The benchmark's own tests run on the CPU, apart from the repo's suite:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import time  # noqa: E402

import pytest  # noqa: E402

# A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the
# real configuration and traffic, with fewer and smaller files.
TINY = {"scan": {"files": 64, "max_file_bytes": 20000},
        "verify": {"files": 40, "max_file_bytes": 150000}}


@pytest.fixture
def run_tiny():
    from perfbench import harness, spec

    def run(cell_name: str, control=None, seed: int = 2**33 + 5) -> dict:
        cell = spec.load_cell(cell_name)
        for part in cell.config["location"]["parts"]:
            part.update(TINY[cell.traffic["round"]])
        return harness.run_cell(cell, seed, 2, False, time.perf_counter(),
                                require_chip=False, control=control)
    return run
