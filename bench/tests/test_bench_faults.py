"""The comparison that decides ``correct`` fails a broken timed path: the
whole run is driven as on the chip, with the served path broken
underneath (``tools/faults.py``), once for each fault a serving cell can
have."""
import pytest
from benchcells import CELLS, small

from tools import faults


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", faults.KINDS)
def test_fault_makes_the_run_incorrect(cell, kind, run_tiny):
    with faults.planted(kind):
        res = run_tiny(cell, seconds=0.3, **small(cell))
    assert res["correct"] is False, res["checks"]
