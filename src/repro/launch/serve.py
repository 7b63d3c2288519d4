"""Serving driver: prefill a batch of prompts then decode N tokens, on any
mesh that fits the local device count (same decode path the dry-run lowers
at 32k/500k scale).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --devices 8 \
      model.n_layers=2 model.d_model=256 model.n_heads=4 model.n_kv_heads=4 \
      model.d_ff=512 model.vocab_size=512 --new-tokens 8

``--telemetry-dir DIR`` streams one versioned ``serve_decode`` JSONL
record per decode step (``latency_s``, ``tokens_per_s``) to
``DIR/telemetry.jsonl`` (schema: ``repro.obs``).  Per-step latencies need
a ``block_until_ready`` per step, so the stream changes decode timing —
only the telemetry run pays that; the default path is untouched.
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--telemetry-dir", default="",
                    help="stream one serve_decode JSONL record per decode "
                         "step here (off when empty; schema: repro.obs)")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp

    from repro.config.base import apply_overrides
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    from repro.models import build_model
    from repro.sharding import rules as rules_mod
    from repro.sharding.context import use_sharding_rules
    from repro.utils import compat
    from repro.utils.cache import init_compile_cache

    init_compile_cache()
    cfg = apply_overrides(get_config(args.arch), tuple(args.overrides))
    model = build_model(cfg)
    n_dev = len(jax.devices())
    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=n_dev >= 512)
    elif n_dev >= 4:
        mesh = make_debug_mesh(n_dev - n_dev % 4)
    else:
        mesh = compat.make_mesh((1, 1), ("data", "model"))
    print(f"mesh {dict(mesh.shape)}; {cfg.model.name} "
          f"({cfg.model.param_count()/1e6:.1f}M params)")

    p_sh = rules_mod.param_shardings(model, cfg, mesh)
    with compat.set_mesh(mesh), use_sharding_rules(mesh):
        params = jax.jit(model.init, out_shardings=p_sh)(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.model.vocab_size)
        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step, donate_argnums=(1,))

        t0 = time.perf_counter()
        if cfg.model.is_encoder_decoder:
            frames = jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.model.encoder_seq_len, cfg.model.d_model))
            logits, cache = jax.jit(model.prefill)(params, prompts, frames)
        else:
            logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
        print(f"prefill {args.batch}x{args.prompt_len}: "
              f"{(time.perf_counter()-t0)*1e3:.0f} ms (incl. compile)")

        sink = None
        if args.telemetry_dir:
            from repro.obs import sinks as obs_sinks
            sink = obs_sinks.JsonlSink(args.telemetry_dir)

        tok = jnp.argmax(logits.reshape(args.batch, -1), -1)[:, None]
        t0 = time.perf_counter()
        for i in range(args.new_tokens):
            ts = time.perf_counter()
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
            if sink is not None:
                # per-step latency needs a per-step sync — telemetry
                # runs trade a little pipelining for the stream
                jax.block_until_ready(tok)
                lat = time.perf_counter() - ts
                sink.emit(obs_sinks.make_record(
                    "serve_decode", i,
                    {"latency_s": lat, "tokens_per_s": args.batch / lat}))
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0
        print(f"decode {args.new_tokens} steps: {dt*1e3:.0f} ms "
              f"({args.batch*args.new_tokens/dt:.1f} tok/s)")
        if sink is not None:
            sink.close()
            print(f"telemetry: {sink.emitted} records -> {sink.path}")


if __name__ == "__main__":
    main()
