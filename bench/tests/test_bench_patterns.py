"""The benchmark's copy of the traffic generators gives the programs the
program's own generator gives, for every cell's patterns and sizes."""
import numpy as np
import pytest

from bench import patterns
from bench.common import derive
from repro.mesh.traffic import make_traffic

SEEDS = [derive(2**31 + 5, 0, 0), derive(7, 0, 3)]
HORIZON = 1000
CASES = (
    [("uniform", 16, 32, 128, 0.5)]
    + [(p, 32, 32, patterns.program_length(0.12, HORIZON), r)
       for p in ("uniform", "transpose", "bit_complement", "tornado")
       for r in (0.03, 0.12)]
    + [(p, 16, 32, patterns.program_length(r, HORIZON), r)
       for p in ("uniform", "bit_complement", "tornado", "hotspot",
                 "neighbor")
       for r in (0.05, 0.3)])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("pattern,nx,ny,length,rate", CASES)
def test_copy_gives_the_programs_generator(pattern, nx, ny, length, rate,
                                           seed):
    got = patterns.make_traffic(pattern, nx, ny, length, rate=rate, seed=seed)
    want = make_traffic(pattern, nx, ny, length, rate=rate, seed=seed)
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_program_length_matches_the_services_sizing():
    from repro.sim_service.request import _program_length
    for load in (0.03, 0.05, 0.1, 0.12, 0.2, 0.3):
        assert patterns.program_length(load, HORIZON) == \
            _program_length(load, HORIZON)
