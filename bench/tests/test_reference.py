"""The plain references against the system, at tiny sizes on the CPU."""
import os
import subprocess
import sys

import jax
import pytest

import tiny
from benchkit import fl_round, fleet_scan, ref_olmo

HERE = os.path.dirname(os.path.abspath(__file__))


def fl_readings(cell, seed, **ref_kw):
    prog = fl_round.Program(cell, jax.devices()[:cell.chips])
    with prog.context():
        params, _, read = fl_round.first_rounds(prog, seed)
    del params
    return fl_round.compare(read, fl_round.reference(cell, seed, **ref_kw))


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 1])
def test_fl_round_matches_the_reference(seed):
    r = fl_readings(tiny.fl_cell(), seed)
    assert r["survivors_gap"] == 0.0
    assert r["loss_gap"] < tiny.TINY_FL_LIMITS["loss_gap"]
    assert r["change_gap"] < tiny.TINY_FL_LIMITS["change_gap"]


def test_fl_round_four_cohorts_matches_the_reference():
    """Four cohorts on four host devices, in a process of its own."""
    code = (
        "import sys, json, jax; sys.path[:0] = %r\n"
        "import tiny, test_reference as t\n"
        "print(json.dumps(t.fl_readings(tiny.fl_cell(4), 11)))\n"
        % (sys.path[:4],))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=HERE, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["survivors_gap"] == 0.0
    assert r["loss_gap"] < tiny.TINY_FL_LIMITS["loss_gap"]
    assert r["change_gap"] < tiny.TINY_FL_LIMITS["change_gap"]


@pytest.mark.parametrize("fleet,seed", [(True, 7), (False, 9)],
                         ids=["fleet", "without_a_fleet"])
def test_fleet_chunk_matches_the_reference(fleet, seed):
    """With the fleet, and as the homogeneous cohort (``fleet.size`` 0)
    that a fleet-bypassing cell would run."""
    cell = tiny.fleet_cell()
    if not fleet:
        for k in ("fleet.size", "power.policy", "fleet.selection"):
            cell.traffic["overrides"].pop(k)
    sim, params, _, _, read = fleet_scan.first_chunk(cell, seed)
    del sim, params
    r = fleet_scan.compare(read, fleet_scan.reference(cell, seed))
    assert r["survivors_gap"] == 0.0 and r["selected_mismatch"] == 0.0
    assert fleet or r["fleet_gap"] == 0.0
    for name in ("loss_gap", "fleet_gap", "change_gap"):
        assert r[name] < tiny.TINY_FLEET_LIMITS[name], (name, r)


def test_reference_round_is_deterministic():
    cell = tiny.fl_cell()
    a = fl_round.reference(cell, 3)
    b = fl_round.reference(cell, 3)
    assert a == b


def test_bf16_dot_rounds_its_inputs():
    x = jax.numpy.asarray([[1.0 + 2 ** -10]])
    assert float(ref_olmo.f32_dot("ij,jk->ik", x, x)[0, 0]) != \
        float(ref_olmo.bf16_dot("ij,jk->ik", x, x)[0, 0])

