"""The four OLMoE faults and the sound program, each served on the CPU at the
cell's published hidden width and read by the cell's own check
(tests/fault_cases.py holds the cut cell and the body)."""
import pytest

from benchmark.tools import fault_readings
from fault_cases import a_fault_the_tolerance_must_catch_fails_it
from fault_cases import olmoe_cut_cell as cut_cell  # noqa: F401  (a fixture)


@pytest.mark.parametrize("fault", [None, *fault_readings.FAULTS])
def test_a_fault_the_tolerance_must_catch_fails_it(fault, cut_cell):  # noqa: F811
    a_fault_the_tolerance_must_catch_fails_it(fault, cut_cell)
