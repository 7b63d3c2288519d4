"""Launcher-layer tests: input specs, sharding knobs, dry-run on a small mesh.

Subprocess-based (XLA_FLAGS must precede jax init; the global suite sees 1
device per the brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess multi-device lowering, minutes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 480,
           env_extra=None, env_drop=()):
    env = dict(os.environ)
    for k in env_drop:
        env.pop(k, None)
    env.update(env_extra or {})
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_lower_combo_small_mesh_reduced():
    """End-to-end dry-run machinery on a reduced arch + debug mesh: lowers,
    compiles, produces all three roofline terms and HLO collective counts."""
    run_py("""
    import dataclasses, jax
    from repro.configs import get_config, reduced
    from repro.launch.dryrun import lower_combo
    cfg = reduced(get_config("olmo-1b"))
    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        # shrink the shape through the config path: reduced() caps seq/batch
        rec = lower_combo("olmo-1b", shape, False, config=cfg, mesh=mesh)
        assert rec["status"] == "OK", rec
        t = rec["roofline"]
        assert t["compute_s"] > 0 and t["memory_s"] > 0
        assert rec["memory"]["peak_estimate_bytes"] > 0
    print("OK")
    """)


def test_decode_seq_over_model_fallback():
    """decode_batch_2d with an indivisible batch falls back to sharding the
    cache sequence dim over `model` — and still lowers+compiles."""
    run_py("""
    import dataclasses, jax
    from repro.config.base import apply_overrides
    from repro.configs import get_config, reduced, for_shape
    from repro.configs.shapes import get_shape
    from repro.launch.inputs import decode_specs
    from repro.launch.dryrun import lower_combo
    from repro.models import build_model

    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    shape = get_shape("decode_32k")
    # batch 128 % 8 == 0 -> full 2D possible on this mesh; force the seq
    # fallback with an odd batch via a custom shape
    shape = dataclasses.replace(shape, global_batch=6)  # 6 % 8 != 0
    cfg = reduced(get_config("qwen2.5-14b"))
    cfg = apply_overrides(cfg, ("train.decode_batch_2d=true",))
    model = build_model(for_shape(cfg, shape))
    (cs, ts), (csh, tsh) = decode_specs(model, for_shape(cfg, shape), shape, mesh)
    specs = [s.spec for s in jax.tree_util.tree_leaves(
        csh, is_leaf=lambda x: hasattr(x, "spec"))]
    # the 5-D kv cache leaves must shard their seq dim over `model`
    kv_specs = [s for s, leaf in zip(specs, jax.tree_util.tree_leaves(cs))
                if getattr(leaf, "ndim", 0) == 5]
    assert kv_specs and all(s[2] == "model" for s in kv_specs), specs
    print("OK")
    """)


def test_zero_over_model_keeps_params_sharded():
    run_py("""
    import jax
    from repro.config.base import apply_overrides
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.sharding.rules import param_specs
    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    model = build_model(base)

    dp = apply_overrides(base, ("train.dp_over_model=true",))
    zero = apply_overrides(base, ("train.zero_over_model=true",
                                  "train.dp_over_model=true"))
    specs_dp = jax.tree_util.tree_leaves(param_specs(model, dp, mesh))
    specs_zero = jax.tree_util.tree_leaves(param_specs(model, zero, mesh))
    assert all("model" not in str(s) for s in specs_dp)
    assert any("model" in str(s) for s in specs_zero)
    print("OK")
    """)


def test_train_driver_runs_a_few_steps():
    """The CLI training driver runs end-to-end on a tiny reduced config."""
    out = run_py("""
    import sys
    sys.argv = ["train", "--arch", "olmo-1b", "--devices", "8",
                "--steps", "2", "--log-every", "1",
                "model.n_layers=2", "model.d_model=128", "model.n_heads=4",
                "model.n_kv_heads=4", "model.d_ff=256",
                "model.vocab_size=512",
                "train.global_batch=24", "train.seq_len=32"]
    from repro.launch.train import main
    main()
    """, timeout=900)
    assert "step kind: fl_round" in out
    # one client cohort per device, on the data axis
    assert "mesh: {'data': 8, 'model': 1}" in out
    assert "done: 2 steps" in out


def test_compile_cache_follows_env_var(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache lands there: the helper
    leaves the directory to JAX and a compile writes its entry there."""
    cache = str(tmp_path / "cache")
    out = run_py(f"""
    import os, jax, jax.numpy as jnp
    from repro.utils.cache import CHECKOUT_CACHE_DIR, init_compile_cache
    assert init_compile_cache() == {cache!r}
    assert jax.config.jax_compilation_cache_dir == {cache!r}
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(64.0)).block_until_ready()
    print("ENTRIES", len(os.listdir({cache!r})))
    """, devices=1, env_extra={
        "JAX_COMPILATION_CACHE_DIR": cache,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert int(out.split("ENTRIES")[1].split()[0]) >= 1


def test_compile_cache_defaults_to_checkout():
    """Without the variable the cache is the fixed, git-ignored
    <checkout>/.jax_cache."""
    want = os.path.join(REPO, ".jax_cache")
    run_py(f"""
    import jax
    from repro.utils.cache import init_compile_cache
    assert init_compile_cache() == {want!r}
    assert jax.config.jax_compilation_cache_dir == {want!r}
    """, devices=1, env_drop=("JAX_COMPILATION_CACHE_DIR",))
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
