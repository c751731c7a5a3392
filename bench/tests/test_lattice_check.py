"""The simulator's check reads one lattice cell of every scheme, and the
references give the same numbers in worker processes as in this one."""
import jax
import pytest

import harness
from benchtest_support import SCALES

CELL = "sim-pr-fig8"


@pytest.fixture(scope="module")
def cell():
    r = harness.resolve(CELL)
    c = r["entry"].Cell(r["cfg"], r["mix"], 2**31 + 77, jax.devices()[:1],
                        SCALES[CELL])
    c.setup()
    for _ in range(3):
        c.unit()
    yield c
    jax.clear_caches()


def test_sample_checks_every_scheme(cell):
    picks = cell.sample()
    assert sorted(i for _, i, _ in picks) == list(range(len(cell.schemes)))
    assert all(0 <= u < len(cell.results) and 0 <= j < len(cell.grid)
               for u, _, j in picks)
    other = harness.resolve(CELL)["entry"].Cell(
        cell.cfg, cell.mix, 2**31 + 78, cell.devs, SCALES[CELL])
    other.results = cell.results
    assert other.sample() != picks                # nets drawn from the seed


def test_check_in_workers_equals_check_here(cell):
    cell.mix = dict(cell.mix, check_workers=1)
    here = cell.check()
    cell.mix = dict(cell.mix, check_workers=2)
    there = cell.check()
    assert here == there and here[0]["value"] <= here[0]["limit"]
