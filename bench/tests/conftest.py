"""Fixtures of the benchmark's own tests. Nothing here touches a TPU."""
import pytest

from benchtest_support import SCALES


@pytest.fixture
def run_cell():
    """Drive a whole run of a cell on the CPU, skipping only the look for
    a chip; returns the result object."""
    import time

    import harness

    def go(workload, seed=2**31 + 5, seconds=0.3, trace=False):
        t0 = time.perf_counter()
        return harness.run(workload, seed, seconds, trace, t_start=t0,
                           require_tpu=False, scale=SCALES[workload],
                           log=lambda msg: None)
    return go
