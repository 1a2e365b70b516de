"""Faults planted in the timed commit, driven through a whole run at a tiny
size on the CPU: ``correct`` has to come out false for each."""
import pytest

from chipbench_tiny import tiny_run

CELLS = ["xlstm-125m.commit-secure8-k8"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_fails_correct(name, fault):
    run = tiny_run(name, seconds=0.05)
    drv = run.cell.driver()
    run.wrap = drv.FAULTS[fault]
    drv.main(run)
    assert not run.correct, run.checks
