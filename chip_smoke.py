"""Smoke test of the system on a TPU: the quickest proof that it still starts.

  python chip_smoke.py              # one chip: phases (a), (b), (c)
  python chip_smoke.py --chips 4    # four chips: phase (d) only

Phases, run in turn in this one process; any failure ends the run with a
non-zero exit code and no result line:

  (a) the distributed FL round through the trainer's own entry point
      (``repro.launch.train.main``): olmo-1b at its published widths and
      full depth, ``--collective packed``, global batch 3 (one microbatch
      per local iteration), seq 2048, 3 steps.  Loss finite every step,
      params finite after.
  (b) the fleet scan: ``FLSimulator.run_rounds`` on mnist_cnn with a
      10^6-device fleet under ``fbl_target`` power, 5 rounds.  Loss,
      energy and battery telemetry finite.
  (c) every Pallas kernel of ``kernels/pack.py`` and ``kernels/quantize.py``
      compiled (``interpret=False``) at bits {8, 2} and d in {421,642 (the
      paper's QNN), 16,777,216} — the chunk kernel at K in {1, 4} — each
      bit-exact against its ``kernels/ref.py`` oracle run on the chip, with
      ``tpu_custom_call`` in every compiled program.
  (d) ``--chips 4`` only: the FL round on a (data=4, model=1) mesh at
      olmo-1b widths in wire modes int/packed/ring/rsag/auto, 2 steps
      each; params and survivors bit-identical to ``int`` on every device
      buffer.

Times printed along the way are smoke timings, not benchmark numbers.  The
last line of standard output is one JSON object naming the device.  There
is no CPU fallback: without a TPU the script exits non-zero before any
phase runs.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: the paper's QNN (mnist_cnn) and a 2^24-element flat wire vector
KERNEL_SIZES = (421_642, 16_777_216)
KERNEL_BITS = (8, 2)
#: (data=4, model=1) phase: the wire modes compared against ``int``
WIRE_MODES = ("int", "packed", "ring", "rsag", "auto")
#: depth of the four-chip round; the K=4 packed and ring wires do not fit
#: 16 GB of HBM per chip at olmo-1b's full 16 layers (compile-time OOM)
FOUR_CHIP_LAYERS = 12

#: XLA compile time (tracing events nest across jit levels; this one does not)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


def _phase(name: str, fn, *args):
    """Run one phase; print its smoke timing (XLA compile vs the rest)."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"smoke timing (not a benchmark) phase={name} wall_s={wall:.1f} "
          f"compile_s={comp:.1f} run_s={wall - comp:.1f}", flush=True)
    return out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(tree))


def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# (a) distributed FL round, one chip
# ---------------------------------------------------------------------------

def phase_fl_round() -> None:
    from repro.launch import train
    res = train.main(["--arch", "olmo-1b", "--collective", "packed",
                      "--steps", "3", "--log-every", "1",
                      "train.global_batch=3", "train.seq_len=2048"])
    _check(res["kind"] == "fl_round", f"step kind {res['kind']}")
    _check(len(res["losses"]) == 3, f"ran {len(res['losses'])} steps")
    _check(all(math.isfinite(x) for x in res["losses"]),
           f"non-finite loss: {res['losses']}")
    _check(_all_finite(res["params"]), "non-finite params after the round")
    print(f"fl_round: losses={res['losses']} "
          f"survivors={float(res['metrics']['survivors'])} "
          f"wire_bits/param={float(res['metrics']['wire_bits_per_param'])}")
    print(f"fl_round: peak_bytes_in_use={_peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# (b) fleet scan, one chip
# ---------------------------------------------------------------------------

def phase_fleet_scan() -> None:
    import dataclasses

    import jax

    from repro.config.base import apply_overrides
    from repro.configs import get_config
    from repro.core.fl import FLSimulator
    from repro.data.pipeline import make_federated_digits
    from repro.models import build_model

    cfg = get_config("mnist_cnn")
    cfg = dataclasses.replace(     # the examples/quickstart.py setting
        cfg,
        channel=dataclasses.replace(cfg.channel, tx_power_w=0.1),
        fl=dataclasses.replace(cfg.fl, devices_per_round=5, local_iters=3,
                               learning_rate=0.05),
        train=dataclasses.replace(cfg.train, global_batch=32))
    cfg = apply_overrides(cfg, ("fleet.size=1000000",
                                "power.policy=fbl_target"))
    store = make_federated_digits(jax.random.PRNGKey(0), num_samples=3000,
                                  num_clients=20, iid=True)
    model = build_model(cfg)
    sim = FLSimulator(model, cfg, store)
    params = model.init(jax.random.PRNGKey(1))
    params, hist = sim.run_rounds(params, 5, jax.random.PRNGKey(2))
    _check(len(hist) == 5, f"{len(hist)} rounds")
    for h in hist:
        for k in ("loss", "energy_j", "battery_total_j", "battery_q50_j"):
            _check(math.isfinite(h[k]), f"round {h['round']} {k}={h[k]}")
    _check(_all_finite(params), "non-finite params after the fleet scan")
    _check(_all_finite(sim.fleet_state), "non-finite fleet state")
    for h in hist:
        print(f"fleet_scan: round={h['round']} loss={h['loss']} "
              f"survivors={h['survivors']} energy_j={h['energy_j']} "
              f"battery_total_j={h['battery_total_j']}")
    print(f"fleet_scan: peak_bytes_in_use={_peak_bytes()}", flush=True)


# ---------------------------------------------------------------------------
# (c) Pallas wire kernels, compiled, against their oracles
# ---------------------------------------------------------------------------

def _kernel_cases(d: int, bits: int):
    """(name, kernel fn, oracle fn, args) for every kernel at (d, bits)."""
    import jax
    import jax.numpy as jnp

    from repro.core import quantization as Q
    from repro.kernels import pack, quantize, ref

    keys = jax.random.split(jax.random.PRNGKey(d + bits), 5)
    g = 2 ** (bits - 1)
    x = jax.random.normal(keys[0], (d,), jnp.float32) * 0.5
    u = jax.random.uniform(keys[1], (d,), jnp.float32)
    codes = jax.random.randint(keys[2], (d,), -g, g, jnp.int32)
    acc = jax.random.randint(keys[3], (d,), -50_000, 50_000, jnp.int32)
    words = Q.pack_codes(codes, bits)
    cases = [
        ("stochastic_quantize_codes",
         lambda x, u: quantize.stochastic_quantize_codes(
             x, u, bits, interpret=False),
         lambda x, u: ref.stochastic_quantize_ref(x, u, bits), (x, u)),
        ("dequantize_codes",
         lambda c: quantize.dequantize_codes(c, bits, interpret=False),
         lambda c: ref.dequantize_ref(c, bits), (codes,)),
        ("quantize_pack",
         lambda x, u: pack.quantize_pack(x, u, bits, interpret=False),
         lambda x, u: ref.quantize_pack_ref(x, u, bits), (x, u)),
        ("unpack_dequantize",
         lambda w: pack.unpack_dequantize(w, bits, d, interpret=False),
         lambda w: ref.unpack_dequantize_ref(w, bits, d), (words,)),
        ("repack",
         lambda w, a: pack.repack(w, a, bits, d, interpret=False),
         lambda w, a: ref.repack_ref(w, a, bits, d), (words, acc)),
        ("pack_sums",
         lambda c: pack.pack_sums(c, bits, interpret=False),
         lambda c: ref.pack_sums_ref(c, bits), (codes,)),
    ]
    for k in (1, 4):
        cases.append((
            f"quantize_pack_chunk[K={k}]",
            lambda x, u, k=k: pack.quantize_pack_chunk(
                x, u, bits, num_chunks=k, interpret=False),
            lambda x, u, k=k: ref.quantize_pack_chunk_ref(
                x, u, bits, num_chunks=k), (x, u)))
    return cases


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    for d in KERNEL_SIZES:
        for bits in KERNEL_BITS:
            for name, kern, oracle, args in _kernel_cases(d, bits):
                compiled = jax.jit(kern).lower(*args).compile()
                _check("tpu_custom_call" in compiled.as_text(),
                       f"{name} d={d} bits={bits}: no tpu_custom_call")
                got = compiled(*args)
                want = jax.jit(oracle)(*args)
                same = jax.tree_util.tree_map(
                    lambda a, b: bool(a.shape == b.shape
                                      and a.dtype == b.dtype
                                      and jnp.array_equal(a, b)), got, want)
                _check(all(jax.tree_util.tree_leaves(same)),
                       f"{name} d={d} bits={bits}: differs from ref.py")
                print(f"kernels: {name} d={d} bits={bits} bit-exact",
                      flush=True)
    # the use_pallas path decides per call, and picks Mosaic on the chip
    x = jnp.zeros((KERNEL_SIZES[0],), jnp.float32)
    text = jax.jit(lambda x, k: ops.quantize_pack(x, k, 8)).lower(
        x, jax.random.PRNGKey(0)).as_text()
    _check("tpu_custom_call" in text, "ops.quantize_pack was interpreted")
    print("kernels: ops dispatch lowers to tpu_custom_call", flush=True)


# ---------------------------------------------------------------------------
# (d) four chips: every wire mode bit-identical to int
# ---------------------------------------------------------------------------

def _buffer_digests(tree):
    """sha256 of every device buffer of every leaf, in a fixed order."""
    import jax
    import numpy as np
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in sorted(leaf.addressable_shards,
                            key=lambda s: s.device.id):
            out.append(hashlib.sha256(
                np.ascontiguousarray(np.asarray(shard.data)).tobytes()
            ).hexdigest())
    return out


def _max_abs_diff(tree, ref) -> float:
    """Largest |tree - ref| over every leaf, on the first device."""
    import jax
    import numpy as np
    return max(float(np.max(np.abs(
        np.asarray(a.addressable_shards[0].data, np.float32) - b)))
        for a, b in zip(jax.tree_util.tree_leaves(tree), ref))


def phase_four_chip_wires() -> None:
    import jax
    import numpy as np

    from repro.launch import train
    digests = {}
    int_params = None        # host copy of int's params, first device
    for mode in WIRE_MODES:
        res = train.main(["--arch", "olmo-1b", "--collective", mode,
                          "--steps", "2", "--log-every", "1",
                          "train.global_batch=12", "train.seq_len=2048",
                          f"model.n_layers={FOUR_CHIP_LAYERS}"])
        _check(dict(res["mesh"].shape) == {"data": 4, "model": 1},
               f"mesh {dict(res['mesh'].shape)}")
        _check(all(math.isfinite(x) for x in res["losses"]),
               f"{mode}: non-finite loss {res['losses']}")
        digests[mode] = (_buffer_digests(res["params"]),
                         _buffer_digests(res["metrics"]["survivors"]))
        if int_params is None:
            int_params = [np.asarray(x.addressable_shards[0].data,
                                     np.float32)
                          for x in jax.tree_util.tree_leaves(res["params"])]
        same = digests[mode] == digests["int"]
        print(f"wires: {mode} losses={res['losses']} survivors="
              f"{float(res['metrics']['survivors'])} wire_bits/param="
              f"{float(res['metrics']['wire_bits_per_param'])} "
              f"buffers={len(digests[mode][0])} bit-identical to int: {same}"
              + ("" if same else " max|params - int|="
                 f"{_max_abs_diff(res['params'], int_params)}"), flush=True)
        del res
    differ = [m for m in WIRE_MODES if digests[m] != digests["int"]]
    _check(not differ, f"params/survivors differ from int: {differ}")
    print(f"wires: {'/'.join(WIRE_MODES)} bit-identical to int on every "
          f"device buffer", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c); 4: the four-chip wire phase")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform} ({dev.device_kind}); no fallback")
    if len(jax.devices()) != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(jax.devices())} devices")

    from repro.utils.cache import init_compile_cache
    print(f"compile cache: {init_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}", flush=True)

    if args.chips == 4:
        _phase("four_chip_wires", phase_four_chip_wires)
    else:
        _phase("fl_round", phase_fl_round)
        _phase("fleet_scan", phase_fleet_scan)
        _phase("kernels", phase_kernels)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
