"""The control: the reference one precision below the configuration's
(float8 matrix operands for the sync cells, 4-bit commit words for the
commit cell), put in the program's place, has to come out not correct,
while the program itself is correct, at a tiny size on the CPU."""
import pytest

from chipbench import harness, lm
from chipbench_tiny import TINY, tiny_run


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_fails_where_program_passes(name):
    run = tiny_run(name)
    drv = run.cell.driver()
    st = drv.setup(run)
    drv.free(st)
    ref = drv.reference(run, st)
    drv.compare(run, st, ref)
    assert run.correct, run.checks
    ctl = harness.Run(run.cell, run.seed, 0.0, False, 0.0)
    key = "delta_norm" if "commit" in name else "loss"
    lm.step_checks(ctl, st["model"].leaf_names(), drv.control(run, st), ref,
                   key)
    assert not ctl.correct, ctl.checks
