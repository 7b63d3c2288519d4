"""The control — the reference in the precision below the configuration's,
put in the program's place — has to come out as not correct.  At the
cells' own sizes on the chip this is ``bench/calibrate.py``; here the same
comparison at a tiny size on the CPU."""
import jax.numpy as jnp
import pytest

import tiny
from benchkit import common, fl_round, fleet_scan
from test_reference import fl_readings


@pytest.mark.parametrize("seed", [21, 2 ** 32 + 22])
def test_bf16_control_fails_the_fl_round(seed):
    cell = tiny.fl_cell()
    ref = fl_round.reference(cell, seed)
    ctrl = fl_round.reference(cell, seed, control=True)
    checks = common.judge(fl_round.compare(ctrl, ref), cell.limits)
    assert not common.all_within(checks), checks
    prog = common.judge(fl_readings(cell, seed), cell.limits)
    assert common.all_within(prog), prog


@pytest.mark.parametrize("seed", [31])
def test_bf16_control_fails_the_fleet_chunk(seed):
    cell = tiny.fleet_cell()
    ref = fleet_scan.reference(cell, seed)
    ctrl = fleet_scan.reference(cell, seed, dt=jnp.bfloat16)
    checks = common.judge(fleet_scan.compare(ctrl, ref), cell.limits)
    assert not common.all_within(checks), checks
