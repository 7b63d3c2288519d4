"""Training driver: federated (the paper's Algorithm 1 as a collective) or
standard data-parallel, on any mesh that fits the local device count.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
      model.n_layers=2 model.d_model=256 model.vocab_size=512 \
      train.global_batch=8 train.seq_len=64 train.steps=10 --devices 8

Fleet mode (``--fleet-size N`` / ``--selection POLICY``, or the
``fleet.*`` config overrides): the FL round draws its cohort from a
stateful heterogeneous device population instead of the paper's
homogeneous i.i.d. sampling — per-device pathloss classes, Gauss-Markov
AR(1) correlated Rayleigh fading carried across rounds, batteries (J)
debited by the §II-D energy model, and per-round availability.  A
jit-able policy (uniform | rate_aware | energy_aware | round_robin; see
``repro.population.selection``) picks one device per cohort shard via a
masked top_k over the WHOLE fleet — dead or sleeping devices are never
selected — and packet errors realize from each device's FBL operating
point (outage ⇒ certain drop).  The ``FleetState`` threads through the
step loop; every collective wire format produces the bit-identical model
under any (fleet, policy) pair.

Per-device power control (``--power-policy`` / ``--power-max``, or the
``power.*`` overrides): instead of the paper's single scalar P_tx, every
device is assigned its own uplink transmit power each round by a jit-able
policy over its current fading/battery state (``repro.population.power``):

  | ``--power-policy``    | per-device power p_i                           |
  |-----------------------|------------------------------------------------|
  | ``fixed``             | ``power.p_fixed`` (0 → ``channel.tx_power_w``);|
  |                       | seed from the §III CMA-ES optimum via          |
  |                       | ``power.calibrate_fixed_power``                |
  | ``channel_inversion`` | truncated inversion to ``power.target_snr_db``,|
  |                       | clipped to [p_min, p_max]                      |
  | ``fbl_target``        | minimum power whose FBL rate at the configured |
  |                       | ``error_prob`` finishes the d·n uplink inside  |
  |                       | ``fl.tau_limit_s`` (lazy scheduling)           |
  | ``lyapunov``          | battery-drift-plus-penalty grid search         |
  |                       | (V = ``power.lyapunov_v``); its score is also  |
  |                       | the ``--selection lyapunov`` cohort policy     |

The assigned powers ride the round metrics (``power_q50_w`` etc. next to
``outage_rate`` vs ``outage_target`` and budget-vs-realized energy) and
persist on the checkpointed ``FleetState`` (``p_last``).

Streaming telemetry (``--telemetry-dir`` / ``--telemetry-every``):

  | flag                  | effect                                         |
  |-----------------------|------------------------------------------------|
  | ``--telemetry-dir D`` | stream one versioned ``train_step`` JSONL      |
  |                       | record per FL round to ``D/telemetry.jsonl``   |
  |                       | WHILE the step executes (one ``io_callback``   |
  |                       | per step; see ``repro.obs``).  Off by default: |
  |                       | the lowered HLO is byte-identical without it.  |
  | ``--telemetry-every N`` | keep every N-th record (default 1 = all)     |

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
      --fleet-size 1000000 --selection lyapunov --power-policy fbl_target \
      --collective auto \
      model.n_layers=2 train.global_batch=8 train.seq_len=64 --devices 8
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, Optional, Sequence

from repro.config.base import (COLLECTIVE_CHOICES, POWER_POLICIES,  # jax-free
                               SELECTION_POLICIES)


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Run the trainer on ``argv`` (default: the command line).

    Returns ``params`` (the trained model, on the mesh), ``losses`` (one
    float per step run), ``metrics`` (the last step's metrics dict),
    ``mesh`` and ``kind`` (the step kind), so callers in the same process
    can check the run.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (0 = real devices)")
    ap.add_argument("--collective", default=None,
                    choices=list(COLLECTIVE_CHOICES),
                    help="wire format; 'auto' picks the byte-minimal mode "
                         "for the mesh (default: quant.wire_format from "
                         "config)")
    ap.add_argument("--fleet-size", type=int, default=0,
                    help="enable the heterogeneous device population with "
                         "this many devices (fleet.size override; 0 keeps "
                         "the paper's homogeneous cohort)")
    ap.add_argument("--selection", default=None,
                    choices=list(SELECTION_POLICIES),
                    help="fleet cohort selection policy (fleet.selection "
                         "override)")
    ap.add_argument("--power-policy", default=None,
                    choices=list(POWER_POLICIES),
                    help="per-device uplink power policy (power.policy "
                         "override; default 'fixed' = the paper's scalar)")
    ap.add_argument("--power-max", type=float, default=0.0,
                    help="cap on the assignable per-device tx power in W "
                         "(power.p_max override)")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--telemetry-dir", default="",
                    help="stream one JSONL telemetry record per FL round "
                         "here while the step executes (off when empty)")
    ap.add_argument("--telemetry-every", type=int, default=1,
                    help="keep every N-th telemetry record (default 1)")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import restore_checkpoint, save_checkpoint, latest_step
    from repro.config.base import apply_overrides
    from repro.configs import get_config
    from repro.core import fl as fl_mod
    from repro.data.synthetic import token_batch
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_cohort_mesh, make_production_mesh
    from repro.models import build_model
    from repro.sharding import rules as rules_mod
    from repro.sharding.context import use_sharding_rules
    from repro.utils import compat
    from repro.utils.cache import init_compile_cache

    init_compile_cache()
    overrides = tuple(args.overrides)
    if args.fleet_size:
        overrides += (f"fleet.size={args.fleet_size}",)
    if args.selection:
        overrides += (f"fleet.selection={args.selection}",)
    if args.power_policy:
        overrides += (f"power.policy={args.power_policy}",)
    if args.power_max:
        overrides += (f"power.p_max={args.power_max}",)
    cfg = apply_overrides(get_config(args.arch), overrides)
    model = build_model(cfg)
    n_dev = len(jax.devices())
    if n_dev >= 512:
        mesh = make_production_mesh(multi_pod=True)
    elif n_dev >= 256:
        mesh = make_production_mesh()
    else:
        mesh = make_cohort_mesh(n_dev)
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.model.name} "
          f"({cfg.model.param_count()/1e6:.1f}M params)")

    steps = args.steps or cfg.train.steps
    collective = fl_mod.resolve_collective(cfg, args.collective)
    sink = tap = None
    if args.telemetry_dir:
        from repro.obs import sinks as obs_sinks
        from repro.obs import tap as obs_tap
        sink = obs_sinks.JsonlSink(args.telemetry_dir)
        # records carry the loop's absolute step index via the tapped
        # step's trailing scalar, so a resumed run appending to an
        # existing telemetry.jsonl stays monotonic in true step index
        tap = obs_tap.step_sink_tap(sink, kind="train_step",
                                      every=max(1, args.telemetry_every))
    step_fn, kind = steps_mod.make_train_step(model, cfg, mesh,
                                              collective=collective, tap=tap)
    if sink is not None and kind == "standard":
        # the standard step has no FL round (and no tap site); close the
        # empty stream rather than leave a half-open file behind
        sink.close()
        sink = tap = None
        print("telemetry: no FL round on this mesh/config — stream off")
    elif sink is not None:
        print(f"telemetry: streaming train_step records -> {sink.path}")
    print(f"step kind: {kind} (collective={collective}, "
          f"quant bits={cfg.quant.bits}, q={cfg.channel.error_prob})")
    fleet = None
    if kind == "fleet_fl_round":
        from repro.population import fleet as pfleet
        fleet = pfleet.init_fleet(jax.random.PRNGKey(cfg.fleet.seed), cfg)
        print(f"fleet: {cfg.fleet.size} devices, "
              f"selection={cfg.fleet.selection}, "
              f"rho={cfg.fleet.fading_rho}, "
              f"battery={cfg.fleet.battery_j}J")

    p_shardings = rules_mod.param_shardings(model, cfg, mesh)
    with compat.set_mesh(mesh), use_sharding_rules(mesh):
        params = jax.jit(model.init, out_shardings=p_shardings)(
            jax.random.PRNGKey(cfg.fl.seed))
        fleet_ckpt_dir = (os.path.join(args.checkpoint_dir, "fleet")
                          if args.checkpoint_dir else "")
        start = 0
        if args.checkpoint_dir and latest_step(args.checkpoint_dir) is not None:
            start = latest_step(args.checkpoint_dir)
            params = restore_checkpoint(args.checkpoint_dir, params)
            print(f"restored checkpoint step {start}")
            if fleet is not None and latest_step(fleet_ckpt_dir) is not None:
                # resume the SAME population: drained batteries, fading
                # chain and cursor — not a fresh round-0 fleet (legacy
                # pre-power-control checkpoints are migrated in place)
                fleet = pfleet.restore_fleet_checkpoint(fleet_ckpt_dir,
                                                        fleet)
                print(f"restored fleet state step "
                      f"{latest_step(fleet_ckpt_dir)}")
        # tapped FL steps take a trailing int32 step scalar (the record's
        # round stamp); untapped signatures are unchanged
        step_shardings = (None,) if tap is not None else ()
        if fleet is not None:
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shardings, None, None, None)
                             + step_shardings,
                             out_shardings=(p_shardings, None, None),
                             donate_argnums=(0,))
        else:
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shardings, None, None)
                             + step_shardings,
                             out_shardings=(p_shardings, None),
                             donate_argnums=(0,))

        key = jax.random.PRNGKey(cfg.fl.seed + 1)
        losses = []
        metrics = {}
        t0 = time.time()
        for step in range(start, steps):
            key, k_data, k_step = jax.random.split(key, 3)
            batch = token_batch(k_data, cfg.train.global_batch,
                                cfg.train.seq_len, cfg.model.vocab_size)
            step_arg = (jnp.int32(step),) if tap is not None else ()
            if fleet is not None:
                params, metrics, fleet = jitted(params, batch, k_step, fleet,
                                                *step_arg)
            else:
                params, metrics = jitted(params, batch, k_step, *step_arg)
            losses.append(metrics["loss"])
            if step % args.log_every == 0:
                loss = float(metrics["loss"])
                tok_s = (cfg.train.global_batch * cfg.train.seq_len
                         * (step - start + 1)) / (time.time() - t0)
                extra = ""
                if "survivors" in metrics:
                    extra = f" survivors={float(metrics['survivors']):.0f}"
                if "wire_bits_per_param" in metrics:
                    extra += (" wire_bits/param="
                              f"{float(metrics['wire_bits_per_param']):.2f}")
                if "battery_q50_j" in metrics:
                    extra += (f" batt_med={float(metrics['battery_q50_j']):.1f}J"
                              f" E_round={float(metrics['cohort_energy_j']):.2f}J")
                if "power_q50_w" in metrics:
                    extra += (f" p_med={float(metrics['power_q50_w']):.3f}W"
                              f" outage={float(metrics['outage_rate']):.3f}")
                print(f"step {step:5d} loss={loss:.4f} tok/s={tok_s:,.0f}{extra}")
            if args.checkpoint_dir and (step + 1) % args.checkpoint_every == 0:
                save_checkpoint(args.checkpoint_dir, step + 1, params)
                if fleet is not None:
                    save_checkpoint(fleet_ckpt_dir, step + 1, fleet)
        print(f"done: {steps - start} steps in {time.time()-t0:.1f}s")
        if sink is not None:
            jax.block_until_ready(params)   # flush in-flight tap callbacks
            sink.close()
            print(f"telemetry: {sink.emitted} records -> {sink.path}")
    return {"params": params, "losses": [float(x) for x in losses],
            "metrics": metrics, "mesh": mesh, "kind": kind}


if __name__ == "__main__":
    main()
