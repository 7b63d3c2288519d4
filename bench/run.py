"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration, traffic
mix and correctness limits are data files under ``bench/``, and each
per-layer metric is a reader of its own under ``bench/metrics/``, which
may declare the device and host spans it reads (``SPANS``,
``HOST_SPANS``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
same window.  Every run checks what its first rounds produced against a
plain reference and prints each compared number beside its limit, last on
standard error and last in the result line.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_reader(name: str):
    """The per-layer metric ``name``'s reader module,
    ``bench/metrics/<name>.py``: its ``read(ctx)`` and the spans it
    declares."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def declared(modules, key: str):
    """The union, in first-declared order, of the names the modules
    declare under ``key``."""
    return tuple(dict.fromkeys(n for m in modules
                               for n in getattr(m, key, ())))


def layer_metrics(spec, cell_name: str):
    """The per-layer metrics this cell reports."""
    return [m for m in spec["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def main(argv=None, *, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    from benchkit import common, driver

    spec = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = common.load_cell(args.workload, spec)
    mods = ({m["name"]: load_reader(m["name"])
             for m in layer_metrics(spec, cell.name)}
            if args.trace else {})
    result = driver.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             {n: m.read for n, m in mods.items()},
                             t_process=T_PROCESS, require_tpu=require_tpu,
                             spans=declared(mods.values(), "SPANS"),
                             host=declared(mods.values(), "HOST_SPANS"))
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in result["metrics"].items()}
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    checks = result.pop("checks")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
