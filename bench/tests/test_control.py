"""The control: the plain reference one precision below the configuration's
(the three-pass ``high`` product for float32 at ``highest``), put in the
program's place, must come out not correct, on every seed tried."""
import pytest

from bench import control, harness, reference


@pytest.mark.parametrize("cell", ["deep96.mixed", "deep96.narrow"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_is_not_correct(cell, seed):
    c = harness.resolve_cell(harness.load_spec(), cell)
    c.cfg = dict(c.cfg, n=16384)
    nums = control.control(c, seed, 1024)
    assert nums["scan_short"]["value"] == 0
    assert nums["foreign_ids"]["value"] == 0
    assert not reference.passes(nums), nums
    assert nums["dist_err"]["value"] > nums["dist_err"]["limit"]
