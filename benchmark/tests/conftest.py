"""Run by hand from the root: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q -p no:cacheprovider`` (tier-1 runs ``tests/`` only)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"lineitem": 4096}   # rows; the functions are the chip's, the size is not


@pytest.fixture(scope="session")
def bench():
    from benchmark import resolve

    return resolve.spec()


@pytest.fixture
def run_tiny():
    """One run of a cell through ``harness.run_cell`` on the CPU at 4,096
    rows, its printed lines captured."""
    from benchmark import harness

    def run(workload, *, trace=False, seed=2**31 + 11, seconds=0.3):
        lines = []
        result = harness.run_cell(
            workload, seed, seconds, trace, platform="cpu", sizes=TINY,
            say=lambda msg, flush=False: lines.append(msg))
        return result, lines

    return run
