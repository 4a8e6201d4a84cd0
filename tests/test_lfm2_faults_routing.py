"""The LFM2 faults made in the routing, and the sound program, each served on
the CPU at the cell's published widths and read by the cell's own check
(tests/fault_cases.py holds the cut cell and the body; three servings of a
2,048-wide model are a worker's share)."""
import pytest

from fault_cases import LFM2_GROUPS, a_fault_the_tolerance_must_catch_fails_it
from fault_cases import lfm2_cut_cell as cut_cell  # noqa: F401  (a fixture)


@pytest.mark.parametrize("fault", LFM2_GROUPS["routing"])
def test_a_fault_the_tolerance_must_catch_fails_it(fault, cut_cell):  # noqa: F811
    a_fault_the_tolerance_must_catch_fails_it(fault, cut_cell)
