"""The control: the reference in the program's place computed in bfloat16
fails the limit, at a size a test run holds."""
import pytest

from bench import control, reference, spec
from bench.tests import fixture


@pytest.mark.parametrize("workload", ["tiny-closed", "tiny-rf"])
def test_bfloat16_control_fails_the_limit(tmp_path, workload):
    root = fixture.make_root(str(tmp_path))
    cell = spec.load_cell(root, workload)
    nums = control.control_numbers(cell, 2**31 + 3, 16)
    assert nums["wrong_answers"] == 0          # counts stay exact
    assert nums["max_rel_err"] > 3 * reference.LIMITS["max_rel_err"]
    assert reference.verdict(nums) is False
