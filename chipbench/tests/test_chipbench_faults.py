"""`correct` comes out false when the timed path is broken underneath:
the control (one resolve round short) and each fault a cell can have."""
import pytest

import _tiny
from chipbench import control

CASES = [(cell, fault) for cell in _tiny.cells()
         for fault in sorted(control.FAULTS)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_makes_the_run_incorrect(cell, fault, tmp_path):
    got = _tiny.run(cell, tmp_path, seed=21, seconds=2.0,
                    plant=control.FAULTS[fault])
    assert got["correct"] is False
    assert got["failed"] > 0
    assert any(v["value"] > v["limit"] for v in got["checks"].values())
