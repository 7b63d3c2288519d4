"""Pallas TPU kernel: int8 x int8 -> int32 quantized matmul with dequant.

The QNN-inference hot-spot the paper motivates (fixed-point arithmetic on the
device).  MXU-aligned 128-multiples block tiling with a K-loop as the leading
grid dimension; the int32 accumulator lives in the output VMEM block across K
steps (revisited because K is the *last* grid axis -> sequential on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 128
BLOCK_N = 128
BLOCK_K = 128


def _qmatmul_kernel(x_ref, w_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul(x_q: jax.Array, w_q: jax.Array, sx: jax.Array, sw: jax.Array, *,
            interpret: bool) -> jax.Array:
    """(M,K) int8 @ (K,N) int8 -> (M,N) f32 scaled by sx*sw (per-tensor)."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)

    pad_m = (BLOCK_M - M % BLOCK_M) % BLOCK_M
    pad_n = (BLOCK_N - N % BLOCK_N) % BLOCK_N
    pad_k = (BLOCK_K - K % BLOCK_K) % BLOCK_K
    xp = jnp.pad(x_q, ((0, pad_m), (0, pad_k)))
    wp = jnp.pad(w_q, ((0, pad_k), (0, pad_n)))
    Mp, Kp = xp.shape
    _, Np = wp.shape
    n_k = Kp // BLOCK_K

    acc = pl.pallas_call(
        functools.partial(_qmatmul_kernel, n_k=n_k),
        grid=(Mp // BLOCK_M, Np // BLOCK_N, n_k),
        in_specs=[
            pl.BlockSpec((BLOCK_M, BLOCK_K), lambda i, j, k: (i, k)),
            pl.BlockSpec((BLOCK_K, BLOCK_N), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        interpret=interpret,
    )(xp, wp)
    out = acc[:M, :N].astype(jnp.float32) * (sx * sw)
    return out
