"""The Pallas wire kernels compile for a TPU v5e (no chip attached).

Each kernel is lowered with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology and compiled by the TPU compiler installed
with jax, at the paper's QNN size (d = 421,642) and at d = 2^24, where a
kernel that asks for more VMEM than the 16 MiB scoped limit is refused.
Nothing runs: this guards what interpret mode cannot see (Mosaic layout
rules, unsupported ops, VMEM budgets) at no chip time.

The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker running this file loads the TPU library; where it cannot be
described the fixture skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import pack, quantize

SIZES = (421_642, 16_777_216)
BITS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler for this platform
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


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
