"""Driver of the FL-round cells: the distributed round of ``launch/train``.

Set-up builds what the trainer builds (``launch.steps.make_train_step`` →
``core.fl.make_fl_round`` on a (cohorts, 1) mesh, the trainer's parameter
shardings, ``donate_argnums=(0,)``), makes the weights on the device in
one jitted call from the seed, and drives that one compiled step through
its first rounds, which the plain reference later repeats.  The window
then dispatches rounds back to back from the same state, as the trainer's
loop does, keeping one round queued behind the one it waits for, and ends
when every dispatched round is done.  Once the window has closed, the
mean of each of the step's scalar metrics over the window's rounds becomes
a counter for the per-layer readers.

The architecture is the configuration file's: its ``reference`` key names
a module under ``bench/benchkit/`` that provides :data:`ARCH_API`, the
plain reference of the round and the yardstick's counts.  A new
architecture comes as such a module and a configuration file that names
it.
"""
from __future__ import annotations

import importlib
import math
import sys
from types import ModuleType
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import common, data, work

#: rounds driven in set-up and repeated by the reference
CHECK_ROUNDS = 3

#: what an architecture module provides: ``dims(cfg)`` the sizes it reads
#: from the program's configuration; ``param_count(dims)``;
#: ``round_flops(dims, tokens, seq_len)`` the model FLOPs of a round's
#: local steps; ``run_rounds(start, rounds, dims, fl, dot, dtype, fault)``
#: the plain reference; ``f32_dot`` and ``bf16_dot`` its products in the
#: configuration's precision and in the control's
ARCH_API = ("dims", "param_count", "round_flops", "run_rounds", "f32_dot",
            "bf16_dot")


def arch(cell: common.Cell) -> ModuleType:
    """The architecture module the cell's configuration file names."""
    return importlib.import_module("benchkit." + cell.config["reference"])


def build_config(cell: common.Cell):
    from repro.config.base import apply_overrides
    from repro.configs import get_config
    c, t = cell.config, cell.traffic
    overrides = [f"{k}={v}" for k, v in c.get("overrides", {}).items()]
    overrides += [f"train.global_batch={t['global_batch']}",
                  f"train.seq_len={t['seq_len']}",
                  f"fl.local_iters={t['local_iters']}",
                  f"quant.bits={t['bits']}",
                  f"channel.error_prob={t['error_prob']}"]
    cfg = apply_overrides(get_config(c["arch"]), tuple(overrides))
    common.check_model(cfg, c["model"])
    return cfg


class Program:
    """The system under test, built as the trainer builds it."""

    def __init__(self, cell: common.Cell, devices):
        from repro.launch import steps as steps_mod
        from repro.models import build_model
        from repro.sharding import rules as rules_mod
        from repro.utils import compat
        self.cell = cell
        self.arch = arch(cell)
        self.cfg = build_config(cell)
        self.model = build_model(self.cfg)
        self.mesh = compat.make_mesh((len(devices), 1), ("data", "model"),
                                     devices=devices)
        step_fn, kind = steps_mod.make_train_step(
            self.model, self.cfg, self.mesh,
            collective=cell.traffic["collective"])
        if kind != "fl_round":
            raise RuntimeError(f"step kind {kind}, expected fl_round")
        self.p_shardings = rules_mod.param_shardings(self.model, self.cfg,
                                                     self.mesh)
        self.step = jax.jit(step_fn,
                            in_shardings=(self.p_shardings, None, None),
                            out_shardings=(self.p_shardings, None),
                            donate_argnums=(0,))
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        t, m = cell.traffic, self.cfg.model
        self.inputs = jax.jit(lambda k: data.round_inputs(
            k, t["global_batch"], t["seq_len"], m.vocab_size))
        self.weights = jax.jit(lambda k: data.make_weights(k, self.shapes),
                               out_shardings=self.p_shardings)
        self.change_norms = jax.jit(lambda p, k: _change_norms(
            p, data.make_weights(k, self.shapes)))

    def context(self):
        """The trainer's mesh and sharding-rule context."""
        import contextlib
        from repro.sharding.context import use_sharding_rules
        from repro.utils import compat
        stack = contextlib.ExitStack()
        stack.enter_context(compat.set_mesh(self.mesh))
        stack.enter_context(use_sharding_rules(self.mesh))
        return stack


def _change_norms(params, start) -> Dict[str, jax.Array]:
    """‖p - p0‖ of every leaf, in float32."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    flat0 = jax.tree_util.tree_leaves(start)
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(
        p.astype(jnp.float32) - p0.astype(jnp.float32))))
        for (path, p), p0 in zip(flat, flat0)}


def keys_for(seed: int):
    base = data.seed_key(seed)
    return jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)


def first_rounds(prog: Program, seed: int):
    """Weights from the seed, then CHECK_ROUNDS rounds through the one
    compiled step.  Returns (params, key, the program's readings)."""
    k_w, key = keys_for(seed)
    params = prog.weights(k_w)
    first: List[Any] = []
    for _ in range(CHECK_ROUNDS):
        key, batch, k_step = prog.inputs(key)
        params, metrics = prog.step(params, batch, k_step)
        first.append((metrics["loss"], metrics["survivors"]))
    norms = prog.change_norms(params, k_w)
    return params, key, {
        "loss": [float(l) for l, _ in first],
        "survivors": [float(s) for _, s in first],
        "change": {k: float(v) for k, v in norms.items()},
        "wire_bits_per_param": float(metrics["wire_bits_per_param"])}


def run(cell: common.Cell, seed: int, seconds: float, devices, counter,
        t_process: float, tracer=None) -> Dict[str, Any]:
    """Set up, measure ``seconds``, return the driver's record."""
    prog = Program(cell, devices)
    with prog.context():
        params, key, prog_read = first_rounds(prog, seed)
        setup_s = common.now() - t_process

        counter.armed = True
        if tracer is not None:
            tracer.start()
        scalars = []
        pending = None
        t0 = common.now()
        with jax.profiler.TraceAnnotation("bench/window"):
            while True:
                with jax.profiler.TraceAnnotation("bench/batch"):
                    key, batch, k_step = prog.inputs(key)
                with jax.profiler.TraceAnnotation("bench/dispatch"):
                    params, metrics = prog.step(params, batch, k_step)
                scalars.append({k: v for k, v in metrics.items()
                                if not isinstance(v, dict)
                                and np.ndim(v) == 0})
                if pending is not None:
                    with jax.profiler.TraceAnnotation("bench/wait"):
                        pending.block_until_ready()
                pending = metrics["loss"]
                if common.now() - t0 >= seconds:
                    break
            with jax.profiler.TraceAnnotation("bench/wait"):
                jax.block_until_ready(params)
        t1 = common.now()
        if tracer is not None:
            tracer.stop()
        counter.armed = False
        scalars = jax.device_get(scalars)
        host_losses = [float(m["loss"]) for m in scalars]

        record = {
            "work": round_work(prog),
            "hlo": (hlo_texts([(prog.step, (params, batch, k_step)),
                               (prog.inputs, (key,))])
                    if tracer is not None else {}),
            "setup_s": setup_s, "window_s": t1 - t0,
            "rounds": len(scalars), "attempted": len(scalars),
            "failed": sum(not math.isfinite(l) for l in host_losses),
            "metrics": {"round_s": (t1 - t0) / len(scalars)},
            "counters": {k: float(np.mean([m[k] for m in scalars]))
                         for k in scalars[0]},
            "device": common.device_info(devices),
            "prog": prog_read,
        }
    print(f"bench: wire bits/param program "
          f"{prog_read['wire_bits_per_param']!r} yardstick "
          f"{record['work']['wire_bits_per_param']!r}", file=sys.stderr)
    return record


def hlo_texts(calls) -> Dict[str, str]:
    """{module name: compiled HLO text} of jitted calls (from the cache)."""
    out = {}
    for fn, args in calls:
        text = fn.lower(*args).compile().as_text()
        out[text.split(None, 2)[1].rstrip(",")] = text
    return out


def resolved_mode(mode: str, bits: int, cohorts: int) -> str:
    """The yardstick's reading of ``auto``: the mode of fewest wire bits
    (ties to the first of ring, rsag, packed, int)."""
    if mode != "auto":
        return mode
    return min(("ring", "rsag", "packed", "int"),
               key=lambda m: work.wire_bits_per_param(m, bits, cohorts))


def round_work(prog: Program) -> Dict[str, float]:
    """FLOPs of a round's local steps and the least bytes of its wire."""
    t, K = prog.cell.traffic, len(prog.mesh.devices.flat)
    a = prog.arch
    dims = a.dims(prog.cfg)
    d = a.param_count(dims)
    bits = work.wire_bits_per_param(
        resolved_mode(t["collective"], t["bits"], K), t["bits"], K)
    wire = work.wire_interface_bytes(
        d, jnp.dtype(prog.cfg.model.dtype).itemsize, bits, K)
    return {"flops_per_round": a.round_flops(
                dims, t["global_batch"] * t["seq_len"], t["seq_len"]),
            "wire_bits_per_param": bits, "wire_hbm_bytes": wire["hbm"],
            "wire_ici_bytes": wire["ici"]}


def reference(cell: common.Cell, seed: int, control: bool = False,
              fault: str = "") -> Dict[str, Any]:
    """The plain reference over the first CHECK_ROUNDS rounds, on one
    device, from the same seed: the architecture module's ``run_rounds``.
    ``control`` runs it in bfloat16, the precision below the
    configuration's float32; ``fault`` as in ``run_rounds``."""
    a = arch(cell)
    cfg = build_config(cell)
    dims = a.dims(cfg)
    t = cell.traffic
    from repro.models import build_model
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    k_w, key = keys_for(seed)
    weights = jax.jit(lambda k: data.make_weights(k, shapes))
    rounds = []
    inputs = jax.jit(lambda k: data.round_inputs(
        k, t["global_batch"], t["seq_len"], cfg.model.vocab_size))
    for _ in range(CHECK_ROUNDS):
        key, batch, k_step = inputs(key)
        rounds.append((batch, k_step))
    fl = {"cohorts": cell.chips, "local_iters": t["local_iters"],
          "bits": t["bits"], "clip": cfg.quant.clip,
          "error_prob": t["error_prob"], "lr": cfg.fl.learning_rate}
    start = weights(k_w)
    dot, dtype = a.f32_dot, jnp.float32
    if control:
        start = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16),
                                       start)
        dot, dtype = a.bf16_dot, jnp.bfloat16
    params, losses, survivors = a.run_rounds(
        start, rounds, dims, fl, dot, dtype, fault)
    norms = jax.jit(_change_norms)(params, weights(k_w))
    return {"loss": losses, "survivors": survivors,
            "change": {k: float(v) for k, v in norms.items()}}


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    return {
        "loss_gap": max(common.rel_gap(a, b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "survivors_gap": max(abs(a - b) for a, b in
                             zip(prog["survivors"], ref["survivors"])),
        "change_gap": common.worst_leaf_gap(prog["change"], ref["change"]),
    }
