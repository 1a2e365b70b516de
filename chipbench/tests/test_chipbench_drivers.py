"""Each driver runs a whole cell at a tiny size on the CPU: set-up, the
window, the reference, and ``correct``."""
import math

import pytest

from chipbench_tiny import TINY, tiny_run


@pytest.mark.parametrize("name", sorted(TINY))
def test_driver_runs_tiny(name):
    run = tiny_run(name)
    out = run.cell.driver().main(run)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(v > 0 and math.isfinite(v)
               for v in out["end_to_end"].values())
    assert run.notes["compiles_in_window"] == 0
    assert run.setup_s > 0
    names = [n for n, _, _ in run.checks]
    assert set(names) == set(run.limits)
    assert run.correct, run.checks
