"""The harness end to end on the CPU at a tiny size (see
``test_benchmark_harness.py``): the timed path broken underneath comes
out as not correct; one of the six faults of ``harness_tiny_cell.py``,
which is one whole run, a file."""

import pytest
from harness_tiny_cell import (  # noqa: F401
    broken_timed_path_is_not_correct, tiny_root)


@pytest.mark.parametrize("fault", ["state_unchanged"])
def test_broken_timed_path_is_not_correct(
        broken_timed_path_is_not_correct, fault):
    broken_timed_path_is_not_correct(fault)
