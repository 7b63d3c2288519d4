"""One run of one cell: device checks, the cell's driver, the trace, the
reference check and the result record."""
from __future__ import annotations

import gc
import importlib
import shutil
import sys
import tempfile
from typing import Any, Callable, Dict, Iterable

from benchkit import common


class Tracer:
    """The JAX profiler over the measured window, into a directory of its
    own under TMPDIR (removed once reduced)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def check_devices(chips: int, require_tpu: bool):
    """The first ``chips`` devices; exits non-zero without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"sees {len(devices)}")
    return devices[:chips]


def run_cell(cell: common.Cell, seed: int, seconds: float, trace: bool,
             readers: Dict[str, Callable], *, t_process: float,
             require_tpu: bool = True, spans: Iterable[str] = (),
             host: Iterable[str] = ()) -> Dict[str, Any]:
    """One run; ``spans`` and ``host`` are the device and host spans the
    readers declare.  The cell's driver module may add host spans of its
    own (``HOST_SPANS``), by which the breakdown names idle gaps."""
    devices = check_devices(cell.chips, require_tpu)
    import jax
    from repro.utils.cache import init_compile_cache
    cache_dir = init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"bench: {cell.name} seed={seed} on {devices[0].device_kind} "
          f"x{len(devices)}, compile cache {cache_dir}", file=sys.stderr)
    counter = common.CompileCounter()
    drv = importlib.import_module("benchkit." + cell.traffic["driver"])
    tracer = Tracer() if trace else None
    try:
        rec = drv.run(cell, seed, seconds, devices, counter, t_process,
                      tracer)
        out: Dict[str, Any] = {"correct": False,
                               "attempted": rec["attempted"],
                               "failed": rec["failed"],
                               "device": rec["device"]}
        if trace:
            from benchkit import trace_reduce
            host = tuple(dict.fromkeys(
                (*host, *getattr(drv, "HOST_SPANS", ()))))
            ctx = trace_reduce.reduce(tracer.dir, rec, devices, spans, host)
            print(f"bench: trace attribution coverage "
                  f"{ctx['coverage']:.4f} of device op time "
                  f"({ctx['provenanced']:.4f} carries an op_name)",
                  file=sys.stderr)
            metrics = {}
            for name, read in readers.items():
                v = read(ctx)
                if v is not None:
                    metrics[name] = v
            out["device"]["busy_s"] = ctx["busy_s"]
            out["device"]["window_s"] = ctx["window_s"]
            out["breakdown"] = ctx["breakdown"]
        else:
            metrics = dict(rec["metrics"], setup_s=rec["setup_s"])
    finally:
        if tracer is not None:
            tracer.close()
    prog = rec["prog"]
    print(f"bench: {rec['rounds']} rounds in {rec['window_s']:.3f} s, "
          f"set-up {rec['setup_s']:.3f} s, compiles in window "
          f"{counter.compiles}, cache loads in window {counter.cache_loads}",
          file=sys.stderr)
    del rec
    gc.collect()
    readings = drv.compare(prog, drv.reference(cell, seed))
    readings["window_compiles"] = float(counter.compiles
                                        + counter.cache_loads)
    checks = common.judge(readings, dict(cell.limits, window_compiles=0.0))
    out["correct"] = bool(common.all_within(checks) and out["failed"] == 0)
    out["metrics"] = metrics
    out["checks"] = checks
    return out
