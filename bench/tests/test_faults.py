"""A run with the timed path broken underneath must read ``correct: false``.

Each test skips the harness's look for a chip, plants one fault the cell
can have in the program, and drives the rest of a run at a tiny size."""
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from benchkit import driver

HERE = os.path.dirname(os.path.abspath(__file__))


def run_tiny(cell, seed=3):
    return driver.run_cell(cell, seed, 0.2, False, {},
                           t_process=time.perf_counter(), require_tpu=False)


def state_unchanged_round(monkeypatch):
    from repro.core import fl
    make = fl.make_fl_round

    def broken(*a, **kw):
        round_fn = make(*a, **kw)

        def same_state(params, *rest):
            _, metrics = round_fn(params, *rest)
            return params, metrics
        return same_state
    monkeypatch.setattr(fl, "make_fl_round", broken)


def half_batch_loss(monkeypatch):
    from repro.models import transformer
    loss = transformer.LM.loss

    def half(self, params, batch, rng=None, **kw):
        s = batch["tokens"].shape[1] // 2
        return loss(self, params, {k: v[:, :s] for k, v in batch.items()},
                    rng, **kw)
    monkeypatch.setattr(transformer.LM, "loss", half)


def test_sound_tiny_run_is_correct():
    assert run_tiny(tiny.fl_cell())["correct"] is True


def test_fl_round_without_the_exchange_is_caught():
    """Four cohorts on four host devices with the cross-cohort sum left
    out (every psum of the round hands back the chip's own value)."""
    code = (
        "import sys, json, jax, time; sys.path[:0] = %r\n"
        "import tiny\n"
        "from benchkit import driver\n"
        "cell = tiny.fl_cell(4)\n"
        "jax.lax.psum = lambda x, axes, **kw: x\n"
        "out = driver.run_cell(cell, 5, 0.2, False, {},\n"
        "    t_process=time.perf_counter(), require_tpu=False)\n"
        "print(json.dumps(out['correct']))\n" % (sys.path[:4],))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=HERE, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) is False


def fleet_unchanged(monkeypatch):
    from repro.population import fleet
    update = fleet.round_update

    def broken(state, *a, **kw):
        _, info = update(state, *a, **kw)
        return state, info
    monkeypatch.setattr(fleet, "round_update", broken)


def fleet_half_batch(monkeypatch):
    from repro.models import cnn
    loss = cnn.CNNModel.loss

    def half(self, params, batch, rng=None, **kw):
        n = batch["labels"].shape[0] // 2
        return loss(self, params, {k: v[:n] for k, v in batch.items()},
                    rng, **kw)
    monkeypatch.setattr(cnn.CNNModel, "loss", half)


FAULTS = [(tiny.fl_cell, state_unchanged_round),
          (tiny.fl_cell, half_batch_loss),
          (tiny.fleet_cell, fleet_unchanged),
          (tiny.fleet_cell, fleet_half_batch)]


@pytest.mark.parametrize("cell,plant", FAULTS,
                         ids=[p.__name__ for _, p in FAULTS])
def test_fault_is_caught(monkeypatch, cell, plant):
    plant(monkeypatch)
    out = run_tiny(cell())
    assert out["correct"] is False, out["checks"]
