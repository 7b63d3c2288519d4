"""Distributed FL-round + sharding tests.

These need >1 device, and XLA_FLAGS must be set before jax initializes —
so each test runs in a fresh subprocess (conftest must NOT set the flag:
smoke tests and benches see 1 device, per the brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess multi-device lowering, minutes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_fl_round_equivalence_paper_vs_int_collective():
    """Both collective modes take a step of the same scale and stay finite;
    with quantization disabled they agree exactly."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch

    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, bits=0),
                              channel=dataclasses.replace(cfg.channel, error_prob=0.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    outs = {}
    with set_mesh(mesh):
        for mode in ("paper", "int"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            p2, m = f(params, batch, jax.random.PRNGKey(2))
            outs[mode] = p2
            assert np.isfinite(float(m["loss"]))
            assert float(m["survivors"]) == 2.0  # q=0 -> all survive
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        outs["paper"], outs["int"])
    assert max(jax.tree_util.tree_leaves(d)) < 1e-6
    print("OK")
    """)


def test_fl_round_quantized_step_close_to_unquantized():
    """8-bit uplink quantization perturbs the aggregated step by <= one
    quantization step per parameter (unbiased stochastic rounding)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch

    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    base = reduced(get_config("qwen2.5-14b"))
    base = dataclasses.replace(base, channel=dataclasses.replace(base.channel, error_prob=0.0))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    res = {}
    with set_mesh(mesh):
        for bits in (0, 8):
            cfg = dataclasses.replace(base, quant=dataclasses.replace(base.quant, bits=bits))
            f = jax.jit(make_fl_round(model, cfg, mesh, collective="paper"))
            p2, _ = f(params, batch, jax.random.PRNGKey(2))
            res[bits] = p2
    step = 1.0/128
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        res[0], res[8])
    assert max(jax.tree_util.tree_leaves(d)) <= step + 1e-5
    print("OK")
    """)


def test_int_collective_emits_integer_allreduce():
    """The beyond-paper quantized collective must put INT types on the wire."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes

    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    model = build_model(cfg)
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    p_structs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with set_mesh(mesh):
        txts = {}
        for mode in ("paper", "int"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            txts[mode] = f.lower(p_structs, batch, rng).compile().as_text()
    assert "s16[" in txts["int"] or "s32[" in txts["int"]
    cb_paper = collective_bytes(txts["paper"])["total"]
    cb_int = collective_bytes(txts["int"])["total"]
    assert cb_int < cb_paper, (cb_int, cb_paper)
    print("collective bytes paper=%d int=%d" % (cb_paper, cb_int))
    """)


def test_packed_collective_strictly_fewer_bytes():
    """The packed wire must beat the int-container wire (which beats f32),
    and be numerically identical to it (same codes, exact lane sums)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    outs, cb = {}, {}
    with set_mesh(mesh):
        for mode in ("paper", "int", "packed"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            outs[mode], m = f(params, batch, jax.random.PRNGKey(2))
            assert np.isfinite(float(m["loss"]))
            txt = f.lower(params, batch, jax.random.PRNGKey(2)).compile().as_text()
            cb[mode] = collective_bytes(txt)["total"]
    assert cb["packed"] < cb["int"] < cb["paper"], cb
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        outs["int"], outs["packed"])
    assert max(jax.tree_util.tree_leaves(d)) == 0.0, "packed must equal int exactly"
    print("collective bytes paper=%d int=%d packed=%d" %
          (cb["paper"], cb["int"], cb["packed"]))
    """)


def test_packed_matches_paper_bitforbit_when_quant_disabled():
    """With quantization off every wire format degenerates to the f32 psum."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, bits=0),
                              channel=dataclasses.replace(cfg.channel, error_prob=0.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    outs = {}
    with set_mesh(mesh):
        for mode in ("paper", "packed"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            outs[mode], _ = f(params, batch, jax.random.PRNGKey(2))
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        outs["paper"], outs["packed"])
    assert max(jax.tree_util.tree_leaves(d)) == 0.0
    print("OK")
    """)


def test_ring_collective_bit_identical_and_075x_bytes():
    """The acceptance bar for the ring wire: on the 8-device debug mesh at
    bits=8 the ring's HLO collective bytes are <= 0.75x the packed psum's,
    the byte ordering is ring < packed < int < paper, and the aggregated
    model is bit-identical to the "int" mode (same codes, exact sums)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    assert cfg.quant.bits == 8
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    outs, cb, wire = {}, {}, {}
    with set_mesh(mesh):
        for mode in ("paper", "int", "packed", "ring"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            outs[mode], m = f(params, batch, jax.random.PRNGKey(2))
            assert np.isfinite(float(m["loss"]))
            wire[mode] = float(m["wire_bits_per_param"])
            txt = f.lower(params, batch, jax.random.PRNGKey(2)).compile().as_text()
            cb[mode] = collective_bytes(txt)["total"]
    assert cb["ring"] < cb["packed"] < cb["int"] < cb["paper"], cb
    # 0.75x up to one u32 word (4 B) of padding rounding: the concatenated
    # packed wire is ceil(n/3) words vs the ring's ceil(n/4), so the exact
    # ratio straddles 3/4 by a word either way
    assert cb["ring"] <= 0.75 * cb["packed"] + 4, cb
    assert "collective-permute" in jax.jit(
        make_fl_round(model, cfg, mesh, collective="ring")
    ).lower(params, batch, jax.random.PRNGKey(2)).compile().as_text()
    want_wire = {"paper": 32.0, "int": 16.0, "packed": 32.0/3, "ring": 8.0}
    assert all(abs(wire[m] - want_wire[m]) < 1e-4 for m in want_wire), wire
    for other in ("int", "packed"):
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            outs[other], outs["ring"])
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, f"ring must equal {other}"
    print("collective bytes paper=%d int=%d packed=%d ring=%d" %
          (cb["paper"], cb["int"], cb["packed"], cb["ring"]))
    """)


def test_ring_bit_exact_across_bits_and_drops():
    """Ring == packed bit-for-bit for bits in {1,2,4,8} with packet drops
    (q=0.5, several rngs), and with quantization off it degenerates to the
    f32 psum exactly."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    base = dataclasses.replace(base, channel=dataclasses.replace(
        base.channel, error_prob=0.5))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    with set_mesh(mesh):
        for bits in (1, 2, 4, 8):
            cfg = dataclasses.replace(base, quant=dataclasses.replace(
                base.quant, bits=bits))
            f_ring = jax.jit(make_fl_round(model, cfg, mesh, collective="ring"))
            f_packed = jax.jit(make_fl_round(model, cfg, mesh, collective="packed"))
            for seed in (2, 3, 4):
                p_r, m_r = f_ring(params, batch, jax.random.PRNGKey(seed))
                p_p, m_p = f_packed(params, batch, jax.random.PRNGKey(seed))
                assert float(m_r["survivors"]) == float(m_p["survivors"])
                d = jax.tree_util.tree_map(
                    lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                    p_r, p_p)
                assert max(jax.tree_util.tree_leaves(d)) == 0.0, (bits, seed)
        cfg0 = dataclasses.replace(base, quant=dataclasses.replace(
            base.quant, bits=0))
        f_ring = jax.jit(make_fl_round(model, cfg0, mesh, collective="ring"))
        f_paper = jax.jit(make_fl_round(model, cfg0, mesh, collective="paper"))
        p_r, _ = f_ring(params, batch, jax.random.PRNGKey(5))
        p_f, _ = f_paper(params, batch, jax.random.PRNGKey(5))
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            p_r, p_f)
        assert max(jax.tree_util.tree_leaves(d)) == 0.0
    print("OK")
    """)


def test_ring_non_pow2_shards_and_all_dropped():
    """A 3-shard cohort ring (non-power-of-two K) stays bit-identical to the
    int psum, and an all-dropped round (q=1) is a no-op."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((3,2), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    base = dataclasses.replace(base, channel=dataclasses.replace(
        base.channel, error_prob=0.3))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    with set_mesh(mesh):
        f_ring = jax.jit(make_fl_round(model, base, mesh, collective="ring"))
        f_int = jax.jit(make_fl_round(model, base, mesh, collective="int"))
        for seed in range(4):
            p_r, m = f_ring(params, batch, jax.random.PRNGKey(seed))
            p_i, _ = f_int(params, batch, jax.random.PRNGKey(seed))
            d = jax.tree_util.tree_map(
                lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                p_r, p_i)
            assert max(jax.tree_util.tree_leaves(d)) == 0.0, seed
        cfg1 = dataclasses.replace(base, channel=dataclasses.replace(
            base.channel, error_prob=1.0))
        f1 = jax.jit(make_fl_round(model, cfg1, mesh, collective="ring"))
        p1, m1 = f1(params, batch, jax.random.PRNGKey(7))
        assert float(m1["survivors"]) == 0.0
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            params, p1)
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, "all-dropped must be a no-op"
    print("OK")
    """, devices=6)


def test_lane_overflow_fallback_surfaces_effective_format():
    """bits=30 on an 8-shard cohort makes the packed/ring/rsag lane 33 bits
    — all three modes (and "auto") must fall back to the int container AND
    report the int container's wire bits in the round telemetry (the
    silent-fallback fix: energy accounting charges the bytes actually
    sent)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.core import aggregation as agg
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((8,1), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, bits=30))
    assert agg.effective_wire_format("packed", cfg.quant, 8) == "int"
    assert agg.effective_wire_format("ring", cfg.quant, 8) == "int"
    assert agg.effective_wire_format("rsag", cfg.quant, 8) == "int"
    assert agg.wire_bits_per_param("ring", cfg.quant, (8,)) == 32.0
    assert agg.wire_bits_per_param("rsag", cfg.quant, (8,)) == 32.0
    assert agg.resolve_auto(cfg.quant, (8,)) == "int"
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 48, 32, cfg.model.vocab_size)
    outs, txts, wire = {}, {}, {}
    with set_mesh(mesh):
        for mode in ("int", "packed", "ring", "rsag", "auto"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            outs[mode], m = f(params, batch, jax.random.PRNGKey(2))
            wire[mode] = float(m["wire_bits_per_param"])
            txts[mode] = f.lower(params, batch,
                                 jax.random.PRNGKey(2)).compile().as_text()
    # telemetry reports the int container (32b), not the requested format
    assert wire == {"int": 32.0, "packed": 32.0, "ring": 32.0,
                    "rsag": 32.0, "auto": 32.0}, wire
    for mode in ("ring", "rsag"):
        assert "collective-permute" not in txts[mode]  # no ring was built
    for mode in ("packed", "ring", "rsag", "auto"):
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            outs["int"], outs[mode])
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, mode
    print("OK")
    """)


def test_rsag_bit_identical_and_wire_accounting():
    """The rsag acceptance bar on the debug mesh: bit-identical to
    "int"/"packed"/"ring", collective-permute on the wire, and honest
    telemetry (9.33 bits/param at n=8, K=2: half the vector at the native
    lane + half at the grown all-gather lane)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    cfg = reduced(get_config("olmo-1b"))
    assert cfg.quant.bits == 8
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    outs, cb, wire = {}, {}, {}
    with set_mesh(mesh):
        for mode in ("paper", "int", "packed", "ring", "rsag"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            outs[mode], m = f(params, batch, jax.random.PRNGKey(2))
            assert np.isfinite(float(m["loss"]))
            wire[mode] = float(m["wire_bits_per_param"])
            txt = f.lower(params, batch, jax.random.PRNGKey(2)).compile().as_text()
            cb[mode] = collective_bytes(txt)["total"]
            if mode == "rsag":
                assert "collective-permute" in txt
    # K=2 regime: ring still wins, but rsag already undercuts packed/int
    assert cb["ring"] < cb["rsag"] < cb["packed"] < cb["int"] < cb["paper"], cb
    assert abs(wire["rsag"] - (0.5 * 8.0 + 0.5 * 32.0 / 3)) < 1e-4, wire
    for other in ("int", "packed", "ring"):
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            outs[other], outs["rsag"])
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, f"rsag must equal {other}"
    print("collective bytes ring=%d rsag=%d packed=%d" %
          (cb["ring"], cb["rsag"], cb["packed"]))
    """)


def test_rsag_bit_exact_across_bits_non_pow2_and_all_dropped():
    """rsag == int bit-for-bit for bits in {1,2,4,8} on a 3-shard cohort
    (non-power-of-two K -> uneven reduce-scatter chunks) with packet drops,
    and an all-dropped round (q=1) is a no-op."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((3,2), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    base = dataclasses.replace(base, channel=dataclasses.replace(
        base.channel, error_prob=0.3))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    with set_mesh(mesh):
        for bits in (1, 2, 4, 8):
            cfg = dataclasses.replace(base, quant=dataclasses.replace(
                base.quant, bits=bits))
            f_rsag = jax.jit(make_fl_round(model, cfg, mesh, collective="rsag"))
            f_int = jax.jit(make_fl_round(model, cfg, mesh, collective="int"))
            for seed in (2, 3):
                p_r, m_r = f_rsag(params, batch, jax.random.PRNGKey(seed))
                p_i, m_i = f_int(params, batch, jax.random.PRNGKey(seed))
                assert float(m_r["survivors"]) == float(m_i["survivors"])
                d = jax.tree_util.tree_map(
                    lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                    p_r, p_i)
                assert max(jax.tree_util.tree_leaves(d)) == 0.0, (bits, seed)
        cfg1 = dataclasses.replace(base, channel=dataclasses.replace(
            base.channel, error_prob=1.0))
        f1 = jax.jit(make_fl_round(model, cfg1, mesh, collective="rsag"))
        p1, m1 = f1(params, batch, jax.random.PRNGKey(7))
        assert float(m1["survivors"]) == 0.0
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            params, p1)
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, "all-dropped must be a no-op"
    print("OK")
    """, devices=6)


def test_multi_axis_cohort_ring_and_rsag_bit_identical():
    """The production cohort shape: FLConfig.cohort_axes defaults to
    ('pod','data'), so ring runs NESTED levels (inter-level repack at the
    sum width) and rsag compounds the partial-sum multiplicity (unit > 1)
    across levels — both must stay bit-identical to "int" and to their own
    Pallas routing on a ('pod','data','model') = (2,2,2) mesh."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    base = reduced(get_config("olmo-1b"))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 16, 32, base.model.vocab_size)
    with set_mesh(mesh):
        f_int = jax.jit(make_fl_round(model, base, mesh, collective="int"))
        p_int, m_int = f_int(params, batch, jax.random.PRNGKey(2))
        assert float(m_int["survivors"]) >= 0.0
        for mode, want_wire in (("ring", 1 * 8.0 + 1 * 32.0 / 3),
                                ("rsag", None)):
            outs = {}
            for pallas in (False, True):
                cfg = dataclasses.replace(base, quant=dataclasses.replace(
                    base.quant, use_pallas=pallas))
                f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
                outs[pallas], m = f(params, batch, jax.random.PRNGKey(2))
                if want_wire is not None:
                    assert abs(float(m["wire_bits_per_param"]) - want_wire) < 1e-4
            for name, other in (("pallas", outs[True]), ("int", p_int)):
                d = jax.tree_util.tree_map(
                    lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                    outs[False], other)
                assert max(jax.tree_util.tree_leaves(d)) == 0.0, (mode, name)
    print("OK")
    """)


def test_auto_collective_resolves_to_byte_minimal_mode():
    """"auto" on the 2x4 debug mesh (K=2) must lower to the ring — same
    HLO collective bytes, a collective-permute on the wire, ring wire bits
    in the telemetry — and its aggregation must equal every concrete mode."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.core import aggregation as agg
    from repro.core.fl import make_fl_round, resolve_collective
    from repro.models import build_model
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes
    from repro.utils.compat import make_mesh, set_mesh

    base = reduced(get_config("olmo-1b"))
    assert agg.resolve_auto(base.quant, (2,)) == "ring"
    assert agg.resolve_auto(base.quant, (16,)) == "packed"
    # the wire_format knob reaches "auto" too
    cfg_wf = dataclasses.replace(base, quant=dataclasses.replace(
        base.quant, wire_format="auto"))
    assert resolve_collective(cfg_wf, None) == "auto"

    mesh = make_mesh((2,4), ("data","model"))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    outs, cb, wire = {}, {}, {}
    with set_mesh(mesh):
        for mode in ("auto", "ring"):
            f = jax.jit(make_fl_round(model, base, mesh, collective=mode))
            outs[mode], m = f(params, batch, jax.random.PRNGKey(2))
            wire[mode] = float(m["wire_bits_per_param"])
            txt = f.lower(params, batch, jax.random.PRNGKey(2)).compile().as_text()
            assert "collective-permute" in txt, mode
            cb[mode] = collective_bytes(txt)["total"]
    assert cb["auto"] == cb["ring"], cb
    assert wire == {"auto": 8.0, "ring": 8.0}, wire
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        outs["auto"], outs["ring"])
    assert max(jax.tree_util.tree_leaves(d)) == 0.0
    print("OK")
    """)


def test_pallas_kernels_routed_into_packed_ring_and_rsag():
    """With use_pallas=True the packed/ring/rsag collectives must execute
    the fused kernels (call-counted at trace time) and match the pure-jnp
    paths bit-exactly (interpret mode on CPU).  Under the default
    ``pipeline_hops`` schedule the ring/rsag front-ends fuse into the
    ``quantize_pack_chunk`` megakernel, which must REPLACE the separate
    front kernels (quantize_pack on the ring, the per-leaf
    stochastic_quantize_codes on rsag) — absence is asserted too."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh
    import repro.kernels.ops as kops

    calls = {}
    for name in ("quantize_pack", "quantize_pack_chunk", "unpack_dequantize",
                 "repack", "pack_sums", "stochastic_quantize_codes"):
        def wrap(orig=getattr(kops, name), name=name):
            def f(*a, **kw):
                calls[name] = calls.get(name, 0) + 1
                return orig(*a, **kw)
            return f
        setattr(kops, name, wrap())

    mesh = make_mesh((2,4), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, base.model.vocab_size)
    CASES = (
        ("packed", ("quantize_pack", "unpack_dequantize"),
         ("quantize_pack_chunk",)),           # hop-free: no megakernel
        ("ring", ("quantize_pack_chunk", "repack"),
         ("quantize_pack",)),                 # megakernel replaces the
                                              # quantize_pack + repack-init
        # rsag: megakernel front (chunking + hop-1 payload), pack_sums for
        # the later payloads, repack accumulates, and the final all-gather
        # stores through the FUSED unpack_dequantize (no int32 round-trip)
        ("rsag", ("quantize_pack_chunk", "pack_sums", "repack",
                  "unpack_dequantize"),
         ("stochastic_quantize_codes",)),     # no per-leaf quantize passes
    )
    with set_mesh(mesh):
        for mode, expected, absent in CASES:
            outs = {}
            for pallas in (False, True):
                calls.clear()
                cfg = dataclasses.replace(base, quant=dataclasses.replace(
                    base.quant, use_pallas=pallas))
                f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
                outs[pallas], _ = f(params, batch, jax.random.PRNGKey(2))
                if pallas:
                    for kernel in expected:
                        assert calls.get(kernel, 0) > 0, (mode, kernel, calls)
                    for kernel in absent:
                        assert calls.get(kernel, 0) == 0, (mode, kernel, calls)
                else:
                    assert not calls, (mode, calls)
            d = jax.tree_util.tree_map(
                lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                outs[False], outs[True])
            assert max(jax.tree_util.tree_leaves(d)) == 0.0, mode
    print("OK")
    """)


def test_pipeline_hops_bit_identical_across_schedules():
    """The double-buffered hop schedule (``pipeline_hops=True``, the
    default) must aggregate BIT-IDENTICALLY to the PR-7 sequential
    schedule under every wire mode — same hops, same accumulation order,
    only the issue order differs — on both the flat (2,4) cohort and the
    nested (2,2,2) multi-axis cohort, with the Pallas kernels in the
    loop (the megakernel front-ends are exercised by the default)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    for shape, axes in (((2, 4), ("data", "model")),
                        ((2, 2, 2), ("pod", "data", "model"))):
        mesh = make_mesh(shape, axes)
        base = reduced(get_config("olmo-1b"))
        base = dataclasses.replace(base, quant=dataclasses.replace(
            base.quant, use_pallas=True))
        model = build_model(base)
        params = model.init(jax.random.PRNGKey(0))
        batch = token_batch(jax.random.PRNGKey(1), 12, 32,
                            base.model.vocab_size)
        with set_mesh(mesh):
            for mode in ("paper", "int", "packed", "ring", "rsag", "auto"):
                outs = {}
                for pipelined in (True, False):
                    cfg = dataclasses.replace(base, quant=dataclasses.replace(
                        base.quant, pipeline_hops=pipelined))
                    f = jax.jit(make_fl_round(model, cfg, mesh,
                                              collective=mode))
                    outs[pipelined], _ = f(params, batch,
                                           jax.random.PRNGKey(2))
                d = jax.tree_util.tree_map(
                    lambda a,b: float(jnp.abs(a.astype(jnp.float32)
                                              -b.astype(jnp.float32)).max()),
                    outs[True], outs[False])
                assert max(jax.tree_util.tree_leaves(d)) == 0.0, (shape, mode)
    print("OK")
    """, timeout=900)


def test_fleet_round_bit_identical_across_collectives():
    """With the population layer enabled (fleet.size > 0) AND an adaptive
    per-device power policy, the distributed round threads a FleetState
    through: power assignment, selection, FBL-tied drops and battery
    debits must be identical under every quantized wire format, so two
    threaded rounds end bit-identical across int/packed/ring/rsag/auto —
    params, batteries AND the assigned power vector — and the metrics
    carry the fleet + power + phase-split telemetry."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.population import fleet as pfleet
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    mesh = make_mesh((2,4), ("data","model"))
    base = reduced(get_config("olmo-1b"))
    cfg = dataclasses.replace(
        base,
        channel=dataclasses.replace(base.channel, error_prob=0.3),
        power=dataclasses.replace(base.power, policy="fbl_target"),
        fleet=dataclasses.replace(base.fleet, size=64,
                                  selection="rate_aware",
                                  harvest_j_per_round=0.05))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    fleet0 = pfleet.init_fleet(jax.random.PRNGKey(cfg.fleet.seed), cfg)
    outs, batts, pows = {}, {}, {}
    with set_mesh(mesh):
        for mode in ("int", "packed", "ring", "rsag", "auto"):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
            p, fleet = params, fleet0
            for seed in (2, 3):
                p, m, fleet = f(p, batch, jax.random.PRNGKey(seed), fleet)
            outs[mode], batts[mode] = p, fleet.battery_j
            pows[mode] = fleet.p_last
            assert np.isfinite(float(m["loss"]))
            assert "wire_phase_bits_per_param" in m
            assert float(m["battery_total_j"]) > 0
            assert float(m["cohort_energy_j"]) >= 0
            assert float(m["power_q50_w"]) >= cfg.power.p_min
            assert float(m["outage_target"]) == np.float32(0.3)
            assert 0.0 <= float(m["outage_rate"]) <= 1.0
            assert float(m["harvested_j"]) >= 0.0
            assert (float(m["energy_budget_j"])
                    >= float(m["cohort_energy_j"]) - 1e-5)
            assert abs(sum(float(v) for v in
                           m["wire_phase_bits_per_param"].values())
                       - float(m["wire_bits_per_param"])) < 1e-4
    for mode in ("packed", "ring", "rsag", "auto"):
        d = jax.tree_util.tree_map(
            lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
            outs["int"], outs[mode])
        assert max(jax.tree_util.tree_leaves(d)) == 0.0, mode
        assert float(jnp.abs(batts["int"] - batts[mode]).max()) == 0.0, mode
        assert float(jnp.abs(pows["int"] - pows[mode]).max()) == 0.0, mode

    # the opt-in IPW correction reaches the distributed round too: still
    # bit-identical across wire formats, and different from the eq.6 run
    cfg_rw = dataclasses.replace(cfg, fleet=dataclasses.replace(
        cfg.fleet, error_reweight=True))
    outs_rw = {}
    with set_mesh(mesh):
        for mode in ("int", "ring"):
            f = jax.jit(make_fl_round(model, cfg_rw, mesh, collective=mode))
            p, m, _ = f(params, batch, jax.random.PRNGKey(2), fleet0)
            outs_rw[mode] = p
            assert np.isfinite(float(m["loss"]))
    d = jax.tree_util.tree_map(
        lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        outs_rw["int"], outs_rw["ring"])
    assert max(jax.tree_util.tree_leaves(d)) == 0.0, "reweight must stay bit-identical"
    print("OK")
    """)


def test_fleet_lambda_consistent_across_model_replicas():
    """Regression: the fleet round must index the cohort-shaped λ vector
    (``FleetRoundInfo.lam``, length num_shards) with the DATA-axes-only
    cohort index.  Where the model axis also runs the body Manual (a
    fully-Manual shard_map), indexing λ with the all-axes flat shard id
    OOB-clamps the gather — model-axis replicas of one cohort then read
    different λ, so the per-device buffers of outputs that out_specs
    declare replicated (params, survivors) silently diverge
    (check_vma=False hides it; a later reshard over "model" — e.g.
    train.py's out_shardings — would mix the divergent columns).  Assert
    every device holds the SAME bytes, on both mesh shapes."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.population import fleet as pfleet
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh

    for shape, names in (((2, 4), ("data", "model")),
                         ((2, 2, 2), ("pod", "data", "model"))):
        mesh = make_mesh(shape, names)
        base = reduced(get_config("olmo-1b"))
        cfg = dataclasses.replace(
            base,
            channel=dataclasses.replace(base.channel, error_prob=0.3),
            power=dataclasses.replace(base.power, policy="fbl_target"),
            fleet=dataclasses.replace(base.fleet, size=64,
                                      selection="rate_aware",
                                      harvest_j_per_round=0.05))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = token_batch(jax.random.PRNGKey(1), 12, 32,
                            cfg.model.vocab_size)
        fleet0 = pfleet.init_fleet(jax.random.PRNGKey(cfg.fleet.seed), cfg)
        with set_mesh(mesh):
            f = jax.jit(make_fl_round(model, cfg, mesh, collective="int"))
            p, fleet = params, fleet0
            for seed in (2, 3, 4):
                p, m, fleet = f(p, batch, jax.random.PRNGKey(seed), fleet)
                surv = set(float(np.asarray(s.data))
                           for s in m["survivors"].addressable_shards)
                assert len(surv) == 1, (shape, seed, surv)
                for leaf in jax.tree_util.tree_leaves(p):
                    ref = np.asarray(leaf.addressable_shards[0].data)
                    for s in leaf.addressable_shards[1:]:
                        assert np.array_equal(ref, np.asarray(s.data)), (
                            shape, seed)
    print("OK")
    """)


def test_wire_format_knob_selects_collective():
    """make_fl_round(collective=None) resolves QuantConfig.wire_format."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round, resolve_collective
    from repro.data.synthetic import token_batch
    from repro.utils.hlo import collective_bytes
    from repro.utils.compat import make_mesh, set_mesh

    base = reduced(get_config("olmo-1b"))
    assert resolve_collective(base, None) == "paper"          # default f32
    for wf, mode in (("f32", "paper"), ("int", "int"), ("packed", "packed"),
                     ("ring", "ring")):
        cfg = dataclasses.replace(base, quant=dataclasses.replace(base.quant,
                                                                  wire_format=wf))
        assert resolve_collective(cfg, None) == mode
        assert resolve_collective(cfg, "int") == "int"        # explicit wins
    try:
        resolve_collective(dataclasses.replace(
            base, quant=dataclasses.replace(base.quant, wire_format="bogus")), None)
        raise SystemExit("expected ValueError")
    except ValueError:
        pass

    mesh = make_mesh((2,4), ("data","model"))
    cfg = dataclasses.replace(base, quant=dataclasses.replace(base.quant,
                                                              wire_format="packed"))
    model = build_model(cfg)
    batch = token_batch(jax.random.PRNGKey(1), 12, 32, cfg.model.vocab_size)
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with set_mesh(mesh):
        f_none = jax.jit(make_fl_round(model, cfg, mesh, collective=None))
        f_expl = jax.jit(make_fl_round(model, cfg, mesh, collective="packed"))
        cb_none = collective_bytes(f_none.lower(p, batch, rng).compile().as_text())
        cb_expl = collective_bytes(f_expl.lower(p, batch, rng).compile().as_text())
    assert cb_none["total"] == cb_expl["total"]
    print("OK")
    """)


def test_error_aware_renormalization_distributed():
    """With q=0.5 some cohorts drop; error-aware aggregation must keep the
    update magnitude ~independent of the survivor count (eq. 6 vs eq. 5)."""
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((4,2), ("data","model"))
    cfg = reduced(get_config("yi-9b"))
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, bits=0),
                              channel=dataclasses.replace(cfg.channel, error_prob=0.5))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = token_batch(jax.random.PRNGKey(1), 16, 32, cfg.model.vocab_size)
    with set_mesh(mesh):
        f = jax.jit(make_fl_round(model, cfg, mesh))
        for seed in range(8):
            p2, m = f(params, batch, jax.random.PRNGKey(seed))
            surv = float(m["survivors"])
            d = jax.tree_util.tree_map(
                lambda a,b: float(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
                params, p2)
            mx = max(jax.tree_util.tree_leaves(d))
            if surv == 0:
                assert mx == 0.0, "all-dropped round must be a no-op"
            else:
                assert mx > 0.0 and np.isfinite(mx)
    print("OK")
    """)


def test_param_specs_divisibility_all_archs():
    """Every derived PartitionSpec divides its dim on the production mesh."""
    run_py("""
    import numpy as np, jax
    from repro.configs import ASSIGNED_ARCHS, get_config
    from repro.models import build_model
    from repro.sharding.rules import param_specs
    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((2,2,2), ("pod","data","model"))
    # divisibility must hold for the REAL mesh sizes; emulate 16-way checks
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        from repro.sharding.rules import ParamRules
        rules = ParamRules(cfg, FakeMesh())
        def check(path, aval):
            spec = rules.spec_for(path, aval)
            for i, entry in enumerate(spec):
                if entry is None: continue
                axs = entry if isinstance(entry, tuple) else (entry,)
                size = 1
                for a in axs: size *= FakeMesh.shape[a]
                assert aval.shape[i] % size == 0, (arch, path, aval.shape, spec)
            return 0
        jax.tree_util.tree_map_with_path(check, shapes)
    print("OK")
    """, devices=8)


def test_long500k_sequence_parallel_decode():
    """batch=1 decode: the KV cache shards its SEQUENCE dim over data."""
    run_py("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config, for_shape
    from repro.configs.shapes import get_shape
    from repro.launch.inputs import decode_specs
    from repro.models import build_model
    from repro.utils.compat import make_mesh, set_mesh
    mesh = make_mesh((4,2), ("data","model"))
    shape = get_shape("long_500k")
    cfg = for_shape(get_config("qwen2.5-14b"), shape)
    model = build_model(cfg)
    (cs, ts), (csh, tsh) = decode_specs(model, cfg, shape, mesh)
    k_sharding = jax.tree_util.tree_leaves(
        csh, is_leaf=lambda x: hasattr(x, "spec"))[0]
    assert "data" in str(k_sharding.spec), k_sharding.spec
    print("OK")
    """)
