"""The Pallas wire kernels and the traced spans compile for a TPU v5e
(no chip attached).

Each kernel is lowered with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology and compiled by the TPU compiler installed
with jax, at the paper's QNN size (d = 421,642) and at d = 2^24, where a
kernel that asks for more VMEM than the 16 MiB scoped limit is refused.
Nothing runs: this guards what interpret mode cannot see (Mosaic layout
rules, unsupported ops, VMEM budgets) at no chip time.

The same compiler decides which ops keep their ``obs.trace`` span: a
tiny packed FL round and a tiny fleet scan are compiled for the chip and
their HLO ``op_name`` metadata is read (the wire's flat buffers under
``wire/quantize_pack``, the simulator's client update under
``sim/client_update``).

The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker running this file loads the TPU library; where it cannot be
described the fixture skips.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import pack, quantize

SIZES = (421_642, 16_777_216)
BITS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler for this platform
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache (its
    # key also ignores op_name metadata, which the span tests read)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(d: int):
    """name -> (fn, [(shape, dtype) per argument]) at size d, bits 8."""
    W = -(-d // (32 // BITS))
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    return {
        "stochastic_quantize_codes": (
            lambda x, u: quantize.stochastic_quantize_codes(
                x, u, BITS, interpret=False), [((d,), f32), ((d,), f32)]),
        "dequantize_codes": (
            lambda c: quantize.dequantize_codes(c, BITS, interpret=False),
            [((d,), i32)]),
        "quantize_pack": (
            lambda x, u: pack.quantize_pack(x, u, BITS, interpret=False),
            [((d,), f32), ((d,), f32)]),
        "unpack_dequantize": (
            lambda w: pack.unpack_dequantize(w, BITS, d, interpret=False),
            [((W,), u32)]),
        "repack": (
            lambda w, a: pack.repack(w, a, BITS, d, interpret=False),
            [((W,), u32), ((d,), i32)]),
        "pack_sums": (
            lambda c: pack.pack_sums(c, BITS, interpret=False),
            [((d,), i32)]),
        "quantize_pack_chunk": (
            lambda x, u: pack.quantize_pack_chunk(
                x, u, BITS, num_chunks=4, interpret=False),
            [((d,), f32), ((d,), f32)]),
    }


@pytest.mark.parametrize("d", SIZES)
@pytest.mark.parametrize("name", sorted(_kernels(8)))
def test_kernel_compiles_for_v5e(one_chip, name, d):
    fn, arg_specs = _kernels(d)[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# spans that survive the TPU compiler
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]")


def _instructions(hlo_text):
    """(name, dtype, dims, op_name or None) of every array instruction in
    every computation of a compiled module."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(3).split(",") if d)
        op = re.search(r'op_name="([^"]+)"', line)
        out.append((m.group(1), m.group(2), dims, op and op.group(1)))
    return out


def test_packed_round_flat_buffers_keep_their_span(topo):
    """In a tiny K = 1 packed round, every buffer write that forms the flat
    code vector s32[d] or the padded word planes u32[cpw * W] (the
    dynamic-update-slices and pads of ``quant.flat_buffer``) carries an
    ``op_name`` under ``wire/quantize_pack``.  The ``jnp.concatenate`` /
    ``jnp.pad`` forms they replace compile to writes with no metadata."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.config.base import apply_overrides
    from repro.configs import get_config
    from repro.core import fl as fl_mod
    from repro.core import quantization as quant
    from repro.models import build_model

    bits = 3  # 10 codes a word: the word planes need a zero tail
    cfg = apply_overrides(get_config("olmo-1b"), (
        "model.n_layers=1", "model.d_model=64", "model.n_heads=2",
        "model.n_kv_heads=2", "model.d_ff=128", "model.vocab_size=256",
        "model.dtype=float32", "train.global_batch=3", "train.seq_len=16",
        "fl.local_iters=3", f"quant.bits={bits}"))
    model = build_model(cfg)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    round_fn = fl_mod.make_fl_round(model, cfg, mesh, collective="packed")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P("data"))
    args = (jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=rep), shapes),
            {k: jax.ShapeDtypeStruct((3, 16), jnp.int32, sharding=batch)
             for k in ("tokens", "labels")},
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    text = jax.jit(round_fn).lower(*args).compile().as_text()

    d = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    padded = quant.codes_per_word(bits) * quant.packed_words(d, bits)
    assert padded > d
    writes = [(name, dt, dims, op) for name, dt, dims, op
              in _instructions(text)
              if (dt, dims) in (("s32", (d,)), ("u32", (padded,)))
              and ("dynamic-update-slice" in name
                   or "dynamic_update_slice" in name
                   or name.startswith("pad"))]
    assert any(dt == "s32" for _, dt, _, _ in writes)
    assert any(dt == "u32" for _, dt, _, _ in writes)
    unspanned = [w for w in writes
                 if not (w[3] and "wire/quantize_pack" in w[3])]
    assert not unspanned, unspanned


def test_fleet_scan_client_update_keeps_its_span(one_chip):
    """In a tiny fleet scan, the QNN's convolutions carry
    ``sim/client_update``, the aggregate and telemetry their own spans,
    and no op sits under both a sim/ and a fleet/ span (siblings)."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.fl import FLSimulator
    from repro.data.pipeline import make_federated_digits
    from repro.models import build_model
    from repro.obs import trace as obs_trace

    cfg = get_config("mnist_cnn")
    cfg = dataclasses.replace(
        cfg, fl=dataclasses.replace(cfg.fl, devices_per_round=4,
                                    local_iters=2),
        train=dataclasses.replace(cfg.train, global_batch=8),
        fleet=dataclasses.replace(cfg.fleet, size=64))
    model = build_model(cfg)
    store = make_federated_digits(jax.random.PRNGKey(0), num_samples=200,
                                  num_clients=8)
    sim = FLSimulator(model, cfg, store)
    stacked, alphas, _ = sim._round_inputs(jax.random.PRNGKey(1))
    rounds = 2
    carry = (jax.eval_shape(model.init, jax.random.PRNGKey(0)),
             sim.fleet_state, jax.random.PRNGKey(2))
    xs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((rounds,) + a.shape, a.dtype),
        (stacked, alphas))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (carry, xs))
    text = sim._fleet_scan_fn(None, False).lower(*args).compile().as_text()

    convs = [line for line in text.splitlines() if " convolution(" in line]
    assert convs and all("sim/client_update" in c for c in convs)
    names = re.findall(r'op_name="([^"]+)"', text)
    for span in obs_trace.SIM_PHASES + ("fleet/select",):
        assert any(span in n for n in names), span
    assert not [n for n in names if "sim/" in n and "fleet/" in n]
