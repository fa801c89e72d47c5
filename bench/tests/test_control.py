"""The control -- the reference with 16-bit registers in the program's
place -- must fail the check on every kind of cell; the same reference
at the stated 32 bits must pass it."""
import pytest

from bench.spec import Cell

CELLS = ["tiny-suite.batch", "tiny-mixed.open"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_is_not_correct(tiny_root, cell, seed):
    from bench import check
    from bench.control import control_run
    c = Cell(tiny_root, cell)
    assert not check.passed(control_run(c, seed, 2.0, 2, bits=16))
    assert check.passed(control_run(c, seed, 2.0, 2, bits=32))
