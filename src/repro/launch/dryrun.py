import os
# a CPU-only rehearsal on 512 forced host devices: never claim a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) combo.

For each combination this:
  1. builds the shape-adapted config and model,
  2. derives divisibility-checked param/batch/cache shardings,
  3. ``jax.jit(step).lower(...).compile()`` against ShapeDtypeStructs
     (no allocation),
  4. records memory_analysis / cost_analysis / parsed collective bytes and
     the three roofline terms into experiments/dryrun/<arch>_<shape>_<mesh>[_<suffix>].json.

train_4k lowers the FL ROUND (the paper's technique: I local steps, quantized
deltas, Bernoulli drops, error-aware renormalizing psum) whenever the
config's cohort axes exist on the mesh; the FSDP archs fall back to the
standard step on the single-pod mesh (DESIGN.md §6).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--collective paper|int] [--skip-existing]
      [--profile-dir DIR] [--telemetry-dir DIR]

``--telemetry-dir`` streams one versioned ``dryrun_combo`` JSONL record
per combo (arch/shape/mesh/status + compile_s and the peak-memory
estimate when OK) to ``DIR/telemetry.jsonl`` as the sweep runs — the
same record stream ``repro.launch.train --telemetry-dir`` writes for FL
rounds (schema: ``repro.obs``).

``--profile-dir`` wraps the whole session in ``jax.profiler.trace``: the
trace/lower/compile work on the forced-device mesh lands as an xplane
artifact under ``DIR/plugins/profile/<ts>/`` (open with TensorBoard or
xprof).
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config.base import POWER_POLICIES, SELECTION_POLICIES, Config
from repro.configs import (ASSIGNED_ARCHS, for_shape, get_config,
                           supports_shape)
from repro.configs.shapes import SHAPES, get_shape
from repro.core import fl as fl_mod
from repro.launch import inputs as inputs_mod
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.sharding import rules as rules_mod
from repro.sharding.context import use_sharding_rules
from repro.utils import compat
from repro.utils import flops as flops_mod
from repro.utils import hlo as hlo_mod
from repro.utils import roofline as roofline_mod

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def rng_struct():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def lower_combo(arch: str, shape_name: str, multi_pod: bool, *,
                collective: Optional[str] = None,
                config: Optional[Config] = None,
                mesh=None, suffix: str = "",
                fleet_overrides: tuple = ()):
    """Lower+compile one combo; returns the result record (dict).

    ``collective=None`` resolves the config's ``quant.wire_format``;
    ``fleet_overrides`` are ``fleet.*`` key=value strings enabling the
    population layer (the FL round then threads a FleetState)."""
    shape = get_shape(shape_name)
    base = config if config is not None else get_config(arch)
    if fleet_overrides:
        from repro.config.base import apply_overrides
        base = apply_overrides(base, fleet_overrides)
    collective = fl_mod.resolve_collective(base, collective)
    if not supports_shape(base, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "SKIP", "reason": "unsupported (see DESIGN.md)"}
    cfg = for_shape(base, shape)
    model = build_model(cfg)
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    p_shardings = rules_mod.param_shardings(model, cfg, mesh)
    p_structs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    t0 = time.time()
    step_kind = shape.kind
    rule_overrides = None
    if (cfg.train.dp_over_model or cfg.train.zero_over_model) and shape.kind == "train":
        rule_overrides = {"batch": (("pod", "data", "model"),
                                    ("pod", "data"), ("data",))}
    if cfg.train.decode_batch_2d and shape.kind == "decode":
        rule_overrides = {"batch": (("pod", "data", "model"),
                                    ("pod", "data"), ("data",))}
    with compat.set_mesh(mesh), use_sharding_rules(mesh, rule_overrides):
        if shape.kind == "train":
            step, kind = steps_mod.make_train_step(model, cfg, mesh,
                                                   collective=collective)
            step_kind = f"train/{kind}"
            b_structs, b_shardings = inputs_mod.train_batch_specs(cfg, shape, mesh)
            if kind == "fleet_fl_round":
                # the fleet threads through replicated; lower with its structs
                from repro.population import fleet as pfleet
                f_structs = jax.eval_shape(
                    lambda k: pfleet.init_fleet(k, cfg), jax.random.PRNGKey(0))
                f_shardings = jax.tree_util.tree_map(lambda _: rng_sh,
                                                     f_structs)
                jitted = jax.jit(step,
                                 in_shardings=(p_shardings, b_shardings,
                                               rng_sh, f_shardings),
                                 out_shardings=(p_shardings, None, None),
                                 donate_argnums=(0,))
                lowered = jitted.lower(p_structs, b_structs, rng_struct(),
                                       f_structs)
            else:
                jitted = jax.jit(step,
                                 in_shardings=(p_shardings, b_shardings, rng_sh),
                                 out_shardings=(p_shardings, None),
                                 donate_argnums=(0,))
                lowered = jitted.lower(p_structs, b_structs, rng_struct())
        elif shape.kind == "prefill":
            step = steps_mod.make_prefill_step(model, cfg)
            structs, shardings = inputs_mod.prefill_specs(cfg, shape, mesh)
            jitted = jax.jit(step, in_shardings=(p_shardings,) + tuple(shardings))
            lowered = jitted.lower(p_structs, *structs)
        else:  # decode
            step = steps_mod.make_decode_step(model, cfg)
            (cache_structs, tok_struct), (cache_sh, tok_sh) = \
                inputs_mod.decode_specs(model, cfg, shape, mesh)
            jitted = jax.jit(step,
                             in_shardings=(p_shardings, cache_sh, tok_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(p_structs, cache_structs, tok_struct)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = hlo_mod.collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    # measured-HLO terms (under-count scan bodies — kept for cross-checking)
    hlo_terms = roofline_mod.derive_terms(
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=float(coll["total"]),
        num_devices=n_dev,
        model_flops_global=roofline_mod.model_flops(cfg, shape))
    # loop-corrected analytic terms (the roofline of record, DESIGN.md §7)
    costs = flops_mod.analytic_costs(cfg, shape, mesh, step_kind=step_kind,
                                     collective_mode=collective)
    terms = roofline_mod.derive_terms(
        flops_per_device=costs.total_flops,
        bytes_per_device=costs.total_bytes,
        collective_bytes_per_device=costs.total_collective,
        num_devices=n_dev,
        model_flops_global=roofline_mod.model_flops(cfg, shape))

    wire = None
    if step_kind.endswith("fl_round"):
        from repro.core import aggregation as agg_mod
        cohort_axes = fl_mod.fl_data_axes(mesh, cfg)
        sizes = tuple(int(mesh.shape[a]) for a in cohort_axes)
        plan = agg_mod.make_wire_plan(collective, cfg.quant, cohort_axes,
                                      sizes)
        wire = {  # the format/bits that actually hit the wire (post-fallback)
            "requested": collective,
            "resolved": plan.resolved,       # what "auto" picked
            "effective": plan.effective,
            "bits_per_param": plan.wire_bits,
            "phase_bits_per_param": agg_mod.wire_phase_bits_per_param(
                collective, cfg.quant, sizes),
        }

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": dict(mesh.shape), "status": "OK",
        "step": step_kind, "collective_mode": collective, "wire": wire,
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "collectives": {k: v for k, v in coll.items() if k != "counts"},
        "collective_counts": coll.get("counts", {}),
        "roofline": terms.as_dict(),
        "roofline_hlo_measured": hlo_terms.as_dict(),
        "analytic_breakdown": {
            "flops": costs.flops,
            "param_bytes": costs.param_bytes,
            "act_bytes": costs.act_bytes,
            "cache_bytes": costs.cache_bytes,
            "collective_bytes": costs.collective_bytes,
        },
        "param_count": cfg.model.param_count(),
        "active_param_count": cfg.model.active_param_count(),
    }
    return record


def _combo_payload(rec: dict) -> dict:
    """The slim ``dryrun_combo`` telemetry payload of one combo record."""
    payload = {"arch": rec["arch"], "shape": rec["shape"],
               "mesh": rec["mesh"], "status": rec["status"]}
    if rec["status"] == "OK":
        payload.update(step=rec["step"], compile_s=rec["compile_s"],
                       peak_estimate_bytes=rec["memory"]
                       ["peak_estimate_bytes"])
    return payload


def run(args) -> int:
    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    sink = None
    if getattr(args, "telemetry_dir", ""):
        from repro.obs import sinks as obs_sinks
        sink = obs_sinks.JsonlSink(args.telemetry_dir)
    combo_index = 0
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                tag = f"{arch}_{shape_name}_{mesh_name}"
                if args.suffix:
                    tag += f"_{args.suffix}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                fleet_overrides = ()
                if args.fleet_size:
                    fleet_overrides += (f"fleet.size={args.fleet_size}",)
                if args.selection:
                    fleet_overrides += (f"fleet.selection={args.selection}",)
                if args.power_policy:
                    fleet_overrides += (f"power.policy={args.power_policy}",)
                if args.power_max:
                    fleet_overrides += (f"power.p_max={args.power_max}",)
                try:
                    rec = lower_combo(arch, shape_name, multi,
                                      collective=args.collective,
                                      suffix=args.suffix,
                                      fleet_overrides=fleet_overrides)
                except Exception as e:  # a failure here is a sharding bug
                    failures += 1
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "FAIL",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if sink is not None:
                    from repro.obs import sinks as obs_sinks
                    sink.emit(obs_sinks.make_record(
                        "dryrun_combo", combo_index, _combo_payload(rec)))
                combo_index += 1
                if rec["status"] == "OK":
                    r = rec["roofline"]
                    print(f"[ok]   {tag:55s} {rec['step']:16s} "
                          f"compile={rec['compile_s']:6.1f}s "
                          f"mem/dev={rec['memory']['peak_estimate_bytes']/2**30:7.2f}GiB "
                          f"terms(c/m/x)={r['compute_s']:.2e}/{r['memory_s']:.2e}/"
                          f"{r['collective_s']:.2e} dom={r['dominant']}")
                elif rec["status"] == "SKIP":
                    print(f"[SKIP] {tag}: {rec['reason']}")
                else:
                    print(f"[FAIL] {tag}: {rec['error']}")
    if sink is not None:
        sink.close()
        print(f"telemetry: {sink.emitted} combo records -> {sink.path}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--collective", default=None,
                    choices=list(fl_mod.COLLECTIVE_CHOICES),
                    help="wire format; 'auto' picks the byte-minimal mode "
                         "for the mesh (default: quant.wire_format from "
                         "config)")
    ap.add_argument("--fleet-size", type=int, default=0,
                    help="enable the device population layer with this many "
                         "devices (fleet.size override)")
    ap.add_argument("--selection", default=None,
                    choices=list(SELECTION_POLICIES),
                    help="fleet cohort selection policy (fleet.selection)")
    ap.add_argument("--power-policy", default=None,
                    choices=list(POWER_POLICIES),
                    help="per-device uplink power policy (power.policy)")
    ap.add_argument("--power-max", type=float, default=0.0,
                    help="cap on assignable per-device tx power in W "
                         "(power.p_max)")
    ap.add_argument("--suffix", default="")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--telemetry-dir", default="",
                    help="stream one dryrun_combo JSONL record per combo "
                         "here (off when empty; schema: repro.obs)")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler trace of the dry-run session "
                         "(trace + compile on the forced-device mesh) to "
                         "DIR/plugins/profile/<ts>/ — open with TensorBoard "
                         "or xprof")
    args = ap.parse_args()
    if args.profile_dir:
        with jax.profiler.trace(args.profile_dir):
            failures = run(args)
    else:
        failures = run(args)
    if failures:
        raise SystemExit(f"{failures} combinations FAILED")


if __name__ == "__main__":
    main()
