"""The control (the references in bfloat16, in the program's place)
fails the limits at a small size, on three seeds, in every cell."""
import pytest

from bench import checks, control
from bench_small import SMALL, small_cell


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_the_control_is_not_correct(cell):
    _, c, cfg, traffic = small_cell(cell)
    for seed in (3, 2**31 + 1, 2**33 + 5):
        vals = control.readings(c, cfg, traffic, seed)
        ok, compared = checks.decide(vals, 0)
        assert not ok, compared
