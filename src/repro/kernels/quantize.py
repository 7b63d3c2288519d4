"""Pallas TPU kernel: stochastic fixed-point quantization (paper §II-B).

Elementwise scale -> add uniform noise -> floor -> clip, the hot transform the
paper applies to every weight/delta each round.  VPU-friendly: the flattened
tensor is viewed as (rows, 128) and tiled into (BLOCK_ROWS, 128) VMEM blocks
(TPU lane width 128, sublane multiples of 8).

Random bits are generated *outside* (threefry) and streamed in as an operand:
TPU-Pallas `pltpu.prng_*` is unavailable in CPU interpret mode, and a pure
kernel is directly comparable against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 128 lanes x 8 sublanes is the v5e native tile; 512 rows keeps the block
# (512*128*4B*3 operands ~ 0.8 MB) comfortably inside the ~16 MB VMEM budget.
BLOCK_ROWS = 512
LANES = 128


def _quantize_kernel(x_ref, u_ref, codes_ref, *, gain: float, g: int,
                     stochastic: bool):
    x = x_ref[...].astype(jnp.float32)
    xq = jnp.clip(x, -1.0, 1.0) * gain  # clip interval folded into gain by caller
    if stochastic:
        rounded = jnp.floor(xq + u_ref[...])
    else:
        rounded = jnp.round(xq)
    codes_ref[...] = jnp.clip(rounded, -g, g - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "clip", "stochastic", "interpret"))
def stochastic_quantize_codes(x: jax.Array, u: jax.Array, bits: int, *,
                              clip: float = 1.0, stochastic: bool = True,
                              interpret: bool) -> jax.Array:
    """Quantize ``x`` to int32 codes using uniform noise ``u`` (same shape)."""
    orig_shape = x.shape
    n = x.size
    # pad flat tensor to a whole number of (BLOCK_ROWS, LANES) tiles
    per_block = BLOCK_ROWS * LANES
    n_pad = (per_block - n % per_block) % per_block
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32) / clip, (0, n_pad))
    uf = jnp.pad(u.reshape(-1).astype(jnp.float32), (0, n_pad))
    rows = xf.size // LANES
    xf = xf.reshape(rows, LANES)
    uf = uf.reshape(rows, LANES)

    gain = float(2 ** (bits - 1))
    g = int(2 ** (bits - 1))
    grid = (rows // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_quantize_kernel, gain=gain, g=g, stochastic=stochastic),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(xf, uf)
    return out.reshape(-1)[:n].reshape(orig_shape)


def _dequantize_kernel(codes_ref, out_ref, *, inv_gain: float):
    out_ref[...] = codes_ref[...].astype(jnp.float32) * inv_gain


@functools.partial(jax.jit, static_argnames=("bits", "clip", "interpret"))
def dequantize_codes(codes: jax.Array, bits: int, *, clip: float = 1.0,
                     interpret: bool) -> jax.Array:
    orig_shape = codes.shape
    n = codes.size
    per_block = BLOCK_ROWS * LANES
    n_pad = (per_block - n % per_block) % per_block
    cf = jnp.pad(codes.reshape(-1), (0, n_pad)).reshape(-1, LANES)
    rows = cf.shape[0]
    inv_gain = clip / float(2 ** (bits - 1))
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, inv_gain=inv_gain),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(cf)
    return out.reshape(-1)[:n].reshape(orig_shape)
