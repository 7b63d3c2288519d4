"""The jitted lax.scan multi-round driver vs the per-round Python loop.

``FLSimulator.run_rounds`` must reproduce the exact per-round PRNG chain,
client sampling and telemetry of looping ``run_round`` — it is the hot path
behind ``train`` and the multi-round benchmarks (fig3/fig4).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fl import FLSimulator
from repro.data.pipeline import make_federated_digits
from repro.models import build_model


def _sim(num_samples=400, **over):
    cfg = get_config("mnist_cnn")
    cfg = dataclasses.replace(
        cfg,
        fl=dataclasses.replace(cfg.fl, devices_per_round=3, local_iters=2,
                               learning_rate=0.05),
        train=dataclasses.replace(cfg.train, global_batch=16), **over)
    model = build_model(cfg)
    store = make_federated_digits(jax.random.PRNGKey(0),
                                  num_samples=num_samples, num_clients=8)
    return model, FLSimulator(model, cfg, store)


def _loop(sim, params, rounds, rng):
    history = []
    for _ in range(rounds):
        rng, k = jax.random.split(rng)
        params, tel = sim.run_round(params, k)
        history.append(tel)
    return params, history


def test_run_rounds_matches_per_round_loop():
    """3-round MNIST-CNN: params bit-identical, telemetry equal."""
    model, sim = _sim()
    params = model.init(jax.random.PRNGKey(1))

    p_loop, tels = _loop(sim, params, 3, jax.random.PRNGKey(2))
    p_scan, hist = sim.run_rounds(params, 3, jax.random.PRNGKey(2))

    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               p_loop, p_scan)
    assert max(jax.tree_util.tree_leaves(d)) == 0.0
    assert len(hist) == 3
    for t, (tel, h) in enumerate(zip(tels, hist)):
        assert h["round"] == t
        np.testing.assert_allclose(h["loss"], tel.loss, rtol=1e-6)
        np.testing.assert_allclose(h["accuracy"], tel.accuracy, rtol=1e-6)
        assert h["survivors"] == tel.survivors
        np.testing.assert_allclose(h["energy_j"], tel.energy_j)
        np.testing.assert_allclose(h["tau_s"], tel.tau_s)


def test_all_dropped_round_is_noop_in_both_drivers():
    """error_prob=1: every client drops, eq. 6 renormalizes over zero mass —
    the round must leave params untouched in the loop AND the scan."""
    cfg = get_config("mnist_cnn")
    model, sim = _sim(channel=dataclasses.replace(cfg.channel, error_prob=1.0))
    params = model.init(jax.random.PRNGKey(3))

    p_loop, tels = _loop(sim, params, 2, jax.random.PRNGKey(4))
    p_scan, hist = sim.run_rounds(params, 2, jax.random.PRNGKey(4))

    for p_out in (p_loop, p_scan):
        d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                                   params, p_out)
        assert max(jax.tree_util.tree_leaves(d)) == 0.0
    assert all(t.survivors == 0 for t in tels)
    assert all(h["survivors"] == 0 for h in hist)


def test_run_rounds_folds_eval_fn_into_scan():
    """A jit-able eval_fn rides inside the scan and matches host-side eval."""
    model, sim = _sim()
    params = model.init(jax.random.PRNGKey(5))
    images = sim.store.data["images"][:64]
    labels = sim.store.data["labels"][:64]

    def eval_fn(p):
        logits = model.forward(p, images)
        return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))

    p_scan, hist = sim.run_rounds(params, 2, jax.random.PRNGKey(6),
                                  eval_fn=eval_fn)
    # replicate with the loop + host-side eval
    p_loop = params
    rng = jax.random.PRNGKey(6)
    for h in hist:
        rng, k = jax.random.split(rng)
        p_loop, _ = sim.run_round(p_loop, k)
        np.testing.assert_allclose(h["accuracy"], float(eval_fn(p_loop)),
                                   rtol=1e-6)
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               p_loop, p_scan)
    assert max(jax.tree_util.tree_leaves(d)) == 0.0


def test_train_uses_scan_and_matches_loop():
    """train() rides run_rounds; history equals the per-round loop's."""
    model, sim = _sim()
    params = model.init(jax.random.PRNGKey(7))
    p_train, hist = sim.train(params, 3, jax.random.PRNGKey(8))
    p_loop, tels = _loop(sim, params, 3, jax.random.PRNGKey(8))
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               p_loop, p_train)
    assert max(jax.tree_util.tree_leaves(d)) == 0.0
    assert [h["survivors"] for h in hist] == [t.survivors for t in tels]


def test_train_early_stop_round_granular():
    """target_accuracy chunks rounds at granularity 1 — the stop round is
    identical to the old per-round loop's."""
    model, sim = _sim()
    params = model.init(jax.random.PRNGKey(9))
    # target so low the very first round reaches it
    _, hist = sim.train(params, 5, jax.random.PRNGKey(10),
                        target_accuracy=1e-6)
    assert len(hist) == 1


# ---------------------------------------------------------------------------
# a chunk's inputs: one compiled draw (rectangular store) vs per-draw
# ---------------------------------------------------------------------------

def _round_inputs_loop(sim, rng, rounds):
    """The per-draw reference: ``rounds`` ``_round_inputs`` over the
    chain (rng, k = split(rng)), stacked on a leading rounds axis."""
    per_round = []
    for _ in range(rounds):
        rng, k = jax.random.split(rng)
        per_round.append(sim._round_inputs(k))
    return jax.tree_util.tree_map(lambda *l: jnp.stack(l), *per_round), rng


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("rounds", [1, 3])
@pytest.mark.parametrize("num_samples", [400, 80],
                         ids=["part_ge_batch", "part_lt_batch"])
def test_chunk_inputs_bit_equal_round_inputs_loop(num_samples, rounds):
    """On a rectangular store the compiled chunk draw gives the per-draw
    loop's batches, alphas, run keys and advanced rng bit for bit, with
    partitions of 50 >= B = 16 (drawn without replacement) and of
    10 < B (drawn with replacement)."""
    _, sim = _sim(num_samples)
    assert sim._part_table is not None
    rng = jax.random.PRNGKey(2 ** 31 + 11)
    _assert_trees_equal(sim._chunk_inputs(rng, rounds),
                        _round_inputs_loop(sim, rng, rounds))


@pytest.mark.parametrize("fleet_size", [64, 0])
def test_run_rounds_batched_inputs_match_per_draw_path(fleet_size):
    """``run_rounds`` on a rectangular store (one compiled draw a chunk)
    gives the params, history and rng of the per-draw path, in fleet
    mode and in legacy mode."""
    cfg = get_config("mnist_cnn")
    model, sim = _sim(fleet=dataclasses.replace(cfg.fleet, size=fleet_size))
    assert sim._part_table is not None
    params = model.init(jax.random.PRNGKey(11))
    fleet0 = sim.fleet_state

    def run():
        sim.fleet_state = fleet0
        return sim.run_rounds(params, 2, jax.random.PRNGKey(12),
                              return_rng=True)

    p_batched, h_batched, r_batched = run()
    sim._part_table = None  # what a ragged store gets: the per-draw path
    p_draw, h_draw, r_draw = run()
    _assert_trees_equal((p_batched, r_batched), (p_draw, r_draw))
    assert h_batched == h_draw


def test_ragged_store_takes_per_draw_path_and_matches_loop():
    """A ragged store (300 samples over 8 clients: partitions of 38 and
    37) keeps the per-draw path, and ``run_rounds`` still reproduces the
    per-round loop."""
    model, sim = _sim(300)
    assert sim._part_table is None
    params = model.init(jax.random.PRNGKey(13))
    p_loop, tels = _loop(sim, params, 2, jax.random.PRNGKey(14))
    p_scan, hist = sim.run_rounds(params, 2, jax.random.PRNGKey(14))
    _assert_trees_equal(p_loop, p_scan)
    assert [h["survivors"] for h in hist] == [t.survivors for t in tels]
