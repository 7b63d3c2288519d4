"""Readings that set a cell's correctness limits, at the cell's own size.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
      [--control-seeds 7,8,9] [--out FILE]

For every seed of ``--seeds`` the program runs its first rounds as a
benchmark run does and is compared with the plain reference: the lower
readings.  For every seed of ``--control-seeds`` the control (the
reference in the precision below the configuration's) and each fault the
cell can have, planted in the reference put in the program's place, are
compared with it: the upper readings.  One JSON line per reading.  It
needs the chip and is not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def emit(out, **rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out is not None:
        out.write(line + "\n")
        out.flush()


def unchanged(ref, start_fleet=None):
    """What a step that hands its state back unchanged would read."""
    prog = dict(ref, change={k: 0.0 for k in ref["change"]})
    if start_fleet is not None:
        prog["fleet"] = start_fleet
    return prog


def calibrate_fl(cell, devices, seeds, control_seeds, out):
    import jax
    from benchkit import fl_round
    prog = fl_round.Program(cell, devices)
    for seed in seeds:
        t = time.perf_counter()
        with prog.context():
            params, _, read = fl_round.first_rounds(prog, seed)
        del params
        ref = fl_round.reference(cell, seed)
        emit(out, seed=seed, kind="program",
             readings=fl_round.compare(read, ref), prog=read, ref=ref,
             seconds=time.perf_counter() - t)
    faults = ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])
    for seed in control_seeds:
        ref = fl_round.reference(cell, seed)
        ctrl = fl_round.reference(cell, seed, control=True)
        emit(out, seed=seed, kind="control",
             readings=fl_round.compare(ctrl, ref), prog=ctrl, ref=ref)
        emit(out, seed=seed, kind="fault:unchanged",
             readings=fl_round.compare(unchanged(ref), ref))
        for f in faults:
            bad = fl_round.reference(cell, seed, fault=f)
            emit(out, seed=seed, kind="fault:" + f,
                 readings=fl_round.compare(bad, ref), prog=bad)
    jax.clear_caches()


def level_flips(prog, ref, cfg):
    """Per leaf: the entries where program and reference differ by more
    than a quarter of one aggregated quantization level (the level
    clip / 2^(n-1) of a client's delta over the K clients it is averaged
    with), and the largest difference in such levels."""
    import numpy as np
    q, K = cfg.quant, cfg.fl.devices_per_round
    level = q.clip / 2 ** (q.bits - 1) / K
    out = {}
    for n in ref:
        d = np.abs(np.asarray(prog[n], np.float64) - ref[n])
        out[n] = [int(np.sum(d > level / 4)), int(d.size),
                  float(d.max() / level)]
    return out


def calibrate_fleet(cell, devices, seeds, control_seeds, out):
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchkit import fleet_scan, ref_fleet
    for seed in seeds:
        t = time.perf_counter()
        sim, params, _, cfg, read = fleet_scan.first_chunk(cell, seed)
        params = {n: np.asarray(v) for n, v in params.items()}
        del sim
        ref = fleet_scan.reference(cell, seed)
        emit(out, seed=seed, kind="program",
             readings=fleet_scan.compare(read, ref),
             loss=[read["loss"], ref["loss"]],
             change=[read["change"], ref["change"]],
             flips=level_flips(params, ref["params"], cfg),
             seconds=time.perf_counter() - t)
    for seed in control_seeds:
        ref = fleet_scan.reference(cell, seed)
        ctrl = fleet_scan.reference(cell, seed, dt=jnp.bfloat16)
        emit(out, seed=seed, kind="control",
             readings=fleet_scan.compare(ctrl, ref))
        cfg = fleet_scan.build_config(cell, seed)
        start = ref_fleet.init_fleet(jax.random.PRNGKey(cfg.fleet.seed),
                                     dataclasses.asdict(cfg), jnp.float32)
        emit(out, seed=seed, kind="fault:unchanged",
             readings=fleet_scan.compare(unchanged(ref, {
                 f: np.asarray(start[f]) for f in ref["fleet"]}), ref))
        bad = fleet_scan.reference(cell, seed, fault="half_batch")
        emit(out, seed=seed, kind="fault:half_batch",
             readings=fleet_scan.compare(bad, ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    from benchkit import common, driver
    cell = common.load_cell(args.workload)
    devices = driver.check_devices(cell.chips, require_tpu=True)
    from repro.utils.cache import init_compile_cache
    init_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None
    try:
        if cell.traffic["driver"] == "fl_round":
            calibrate_fl(cell, devices, seeds, controls, out)
        else:
            calibrate_fleet(cell, devices, seeds, controls, out)
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
