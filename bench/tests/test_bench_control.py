"""The control: the plain reference at the next precision below the
configuration's (three bfloat16 passes for float32 at ``highest``) put in
the program's place fails the cell's limits, while the program passes them.
The control's arithmetic is written out in the reference, so it reads the
same here as on the chip; each cell runs its own slots and window
lengths."""
import pytest
from benchcells import CELLS

from harness import check

STATES = ("state_med", "state_max")


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [2**33 + 17, 41])
def test_control_fails_where_the_program_passes(name, seed, run_tiny):
    res, got = run_tiny(name, seconds=0.5, keep=True, seed=seed)
    assert res["correct"] is True, res["checks"]
    ctl = check.state_stats(got["ref"], got["params"], got["pool"],
                            got["rows"], got["pairs"], "high")
    assert any(ctl[k] > res["checks"][k]["limit"] for k in STATES
               if k in res["checks"]), ctl
