"""The precision control, at toy size: the plain reference computed in
bfloat16 and put in the program's place, run through the harness as a
run of the cell runs the program, reads ``correct`` false."""
import pytest
from _bench_toy import spreader

from bench import control


@pytest.mark.parametrize("seed", [2**31 + 5, 3000000123])
def test_bfloat16_control_is_not_correct(seed):
    out = control.control(spreader(), seed=seed)
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["label_mismatch"]["value"] > 0
    assert out["compared"]["fits_differ"]["value"] == 0
