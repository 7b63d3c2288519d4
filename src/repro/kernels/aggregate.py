"""Pallas TPU kernel: error-aware masked weighted aggregation (paper eq. 6).

Server-side hot loop: out[d] = Σ_k w_k·u[k,d] / max(Σ_k w_k, eps) with
w_k = α_k·λ_k (data weight x Bernoulli reliability).  The update matrix is
tiled along D into VMEM blocks; the K (clients-per-round) axis is small
(paper: K=10) and kept resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 2048
LANES = 128


def _aggregate_kernel(u_ref, w_ref, out_ref, *, eps: float):
    w = w_ref[...].astype(jnp.float32)              # (K, 1)
    u = u_ref[...].astype(jnp.float32)              # (K, BLOCK_D)
    den = jnp.maximum(jnp.sum(w), eps)
    out_ref[...] = (jnp.sum(u * w, axis=0, keepdims=True) / den)


@functools.partial(jax.jit, static_argnames=("interpret", "eps"))
def masked_aggregate(updates: jax.Array, weights: jax.Array, *,
                     eps: float = 1e-12, interpret: bool) -> jax.Array:
    """updates (K, D) f32/int; weights (K,) -> (D,) f32 (paper eq. 6)."""
    K, D = updates.shape
    pad_d = (BLOCK_D - D % BLOCK_D) % BLOCK_D
    up = jnp.pad(updates.astype(jnp.float32), ((0, 0), (0, pad_d)))
    Dp = up.shape[1]
    w2 = weights.astype(jnp.float32).reshape(K, 1)

    out = pl.pallas_call(
        functools.partial(_aggregate_kernel, eps=eps),
        grid=(Dp // BLOCK_D,),
        in_specs=[
            pl.BlockSpec((K, BLOCK_D), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_D), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
    )(up, w2)
    return out[0, :D]
