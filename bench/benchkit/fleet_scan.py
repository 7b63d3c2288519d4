"""Driver of the fleet-scan cells: ``FLSimulator.run_rounds`` over chunks.

Set-up makes the digit store and the QNN weights from the seed, builds the
simulator (which draws its fleet from ``fleet.seed``, set from the seed
too), and runs the first chunk of rounds, which the plain reference later
repeats.  The window calls ``run_rounds`` again and again with a fixed
chunk of rounds, host-side input preparation and history expansion
included: that is what a user of the simulator waits for.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import common, data, ref_fleet, work
# the simulator's host stages, by which a traced run's breakdown names the
# device's idle gaps
from repro.obs.trace import SIM_HOST_SPANS as HOST_SPANS

#: the fleet vectors compared after the first chunk
FLEET_FIELDS = ("h_re", "h_im", "battery_j", "p_last", "available")


def fleet_seed(seed: int) -> int:
    """``fleet.seed`` for a run: the fleet follows the benchmark's seed."""
    return int(data.seed_key(seed)[1]) & 0x7FFFFFFF


def build_config(cell: common.Cell, seed: int):
    from repro.config.base import apply_overrides
    from repro.configs import get_config
    c, t = cell.config, cell.traffic
    overrides = [f"{k}={v}" for k, v in c.get("overrides", {}).items()]
    overrides += [f"{k}={v}" for k, v in t.get("overrides", {}).items()]
    overrides.append(f"fleet.seed={fleet_seed(seed)}")
    cfg = apply_overrides(get_config(c["arch"]), tuple(overrides))
    common.check_model(cfg, c["model"])
    return cfg


def keys_for(seed: int):
    base = data.seed_key(seed)
    return (jax.random.fold_in(base, 0), jax.random.fold_in(base, 1),
            jax.random.fold_in(base, 2))


def _store(cell: common.Cell, k_data):
    t = cell.traffic
    return data.federated_digits(k_data, t["num_samples"], t["num_clients"])


def _change_norms(params, start) -> Dict[str, jax.Array]:
    return {n: jnp.sqrt(jnp.sum(jnp.square(params[n] - start[n])))
            for n in params}


def first_chunk(cell: common.Cell, seed: int):
    """Store, weights and simulator from the seed, then the first chunk.
    Returns (simulator, params, key, config, the program's readings)."""
    from repro.core.fl import FLSimulator
    from repro.data.pipeline import ClientStore
    from repro.models import build_model
    cfg = build_config(cell, seed)
    chunk = int(cell.traffic["chunk_rounds"])
    k_w, k_data, key = keys_for(seed)
    store_data, parts = _store(cell, k_data)
    model = build_model(cfg)
    sim = FLSimulator(model, cfg, ClientStore(store_data, parts))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    weights = jax.jit(lambda k: data.make_weights(k, shapes))
    params = weights(k_w)
    key, k_chunk = jax.random.split(key)
    params, hist = sim.run_rounds(params, chunk, k_chunk)
    norms = jax.jit(_change_norms)(params, weights(k_w))
    return sim, params, key, cfg, {
        "loss": [h["loss"] for h in hist],
        "survivors": [float(h["survivors"]) for h in hist],
        "selected": [h.get("selected", []) for h in hist],
        "change": {k: float(v) for k, v in norms.items()},
        "fleet": {f: np.asarray(getattr(sim.fleet_state, f))
                  for f in FLEET_FIELDS} if cfg.fleet.enabled else {}}


def run(cell: common.Cell, seed: int, seconds: float, devices, counter,
        t_process: float, tracer=None) -> Dict[str, Any]:
    chunk = int(cell.traffic["chunk_rounds"])
    sim, params, key, cfg, prog_read = first_chunk(cell, seed)
    setup_s = common.now() - t_process

    counter.armed = True
    if tracer is not None:
        tracer.start()
    chunks, failed = 0, 0
    t0 = common.now()
    with jax.profiler.TraceAnnotation("bench/window"):
        while True:
            key, k_chunk = jax.random.split(key)
            with jax.profiler.TraceAnnotation("bench/run_rounds"):
                params, hist = sim.run_rounds(params, chunk, k_chunk)
            chunks += 1
            failed += sum(not math.isfinite(h["loss"]) for h in hist)
            if common.now() - t0 >= seconds:
                break
    t1 = common.now()
    if tracer is not None:
        tracer.stop()
    counter.armed = False
    rounds = chunks * chunk
    fl = cfg.fl
    work_counts = {"flops_per_round": work.qnn_round_flops(
        fl.devices_per_round, fl.local_iters, cfg.train.global_batch)}
    if cfg.fleet.enabled:
        work_counts["fleet_bytes"] = work.fleet_state_bytes(cfg.fleet.size)
    return {
        "work": work_counts,
        "hlo": scan_hlo(sim, params, key, chunk) if tracer is not None
        else {},
        "setup_s": setup_s, "window_s": t1 - t0, "rounds": rounds,
        "attempted": rounds, "failed": failed,
        "metrics": {"scan_round_ms": (t1 - t0) / rounds * 1e3},
        "device": common.device_info(devices),
        "prog": prog_read,
    }


def scan_hlo(sim, params, key, chunk: int) -> Dict[str, str]:
    """{module name: HLO text} of the simulator's compiled round scan."""
    per_round = []
    for _ in range(chunk):
        key, k = jax.random.split(key)
        per_round.append(sim._round_inputs(k)[:2])
    xs = jax.tree_util.tree_map(lambda *l: jnp.stack(l), *per_round)
    if sim.fleet_state is not None:
        fn = sim._fleet_scan_fn(None, False)
        args = ((params, sim.fleet_state, key), xs)
    else:
        fn = sim._scan_fn(None, False)
        ks = jax.random.split(key, chunk)
        args = (params, (xs[0], xs[1], ks))
    text = fn.lower(*args).compile().as_text()
    return {text.split(None, 2)[1].rstrip(","): text}


def reference(cell: common.Cell, seed: int, dt=jnp.float32, fault: str = ""
              ) -> Dict[str, Any]:
    """The plain reference over the first chunk, from the same seed
    (``dt`` and ``fault`` as in ``ref_fleet.run_chunk``)."""
    from repro.models import build_model
    cfg = build_config(cell, seed)
    cfg_d = dataclasses.asdict(cfg)
    chunk = int(cell.traffic["chunk_rounds"])
    k_w, k_data, key = keys_for(seed)
    store_data, parts = _store(cell, k_data)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    weights = jax.jit(lambda k: data.make_weights(k, shapes))
    num_params = sum(int(np.prod(s.shape))
                     for s in jax.tree_util.tree_leaves(shapes))
    _, k_chunk = jax.random.split(key)
    params, state, records = ref_fleet.run_chunk(
        weights(k_w), jax.random.PRNGKey(cfg.fleet.seed), k_chunk, chunk,
        store_data, parts, cfg_d, num_params, dt, fault)
    start = weights(k_w)
    norms = {n: float(jnp.sqrt(jnp.sum(jnp.square(
        params[n].astype(jnp.float32) - start[n])))) for n in params}
    return {"loss": [r["loss"] for r in records],
            "survivors": [r["survivors"] for r in records],
            "selected": [r["selected"] for r in records],
            "change": norms,
            "params": {n: np.asarray(v, np.float32) for n, v in params.items()},
            "fleet": {f: np.asarray(state[f], np.float32)
                      for f in FLEET_FIELDS} if state is not None else {}}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    fleet_gap = 0.0
    for f in prog["fleet"]:
        a = np.asarray(prog["fleet"][f], np.float64)
        b = np.asarray(ref["fleet"][f], np.float64)
        nb = float(np.linalg.norm(b))
        fleet_gap = max(fleet_gap, float(np.linalg.norm(a - b))
                        / (nb if nb else 1.0))
    return {
        "loss_gap": max(common.rel_gap(a, b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "survivors_gap": max(abs(a - b) for a, b in
                             zip(prog["survivors"], ref["survivors"])),
        "selected_mismatch": float(sum(
            a != b for a, b in zip(prog["selected"], ref["selected"]))),
        "fleet_gap": fleet_gap,
        "change_gap": common.worst_leaf_gap(prog["change"], ref["change"]),
    }
