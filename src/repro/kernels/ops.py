"""Public jit'd wrappers around the Pallas kernels.

This is the one place that picks how a kernel runs: compiled to Mosaic
(``interpret=False``) when the default backend is a TPU, and the Pallas
interpreter everywhere else (the CPU test backend).  The choice is made
per call, when the wrapper traces — never at import, so importing
``repro.kernels`` does not initialise a backend or claim a device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import aggregate as _agg
from repro.kernels import pack as _pack
from repro.kernels import qmatmul as _qmm
from repro.kernels import quantize as _quant
from repro.obs import trace as _obs_trace


def _interpret() -> bool:
    """Interpret the kernels unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def stochastic_quantize_codes(x: jax.Array, key: jax.Array, bits: int, *,
                              clip: float = 1.0, stochastic: bool = True) -> jax.Array:
    u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    with _obs_trace.phase_span("pallas/stochastic_quantize_codes"):
        return _quant.stochastic_quantize_codes(x, u, bits, clip=clip,
                                                stochastic=stochastic,
                                                interpret=_interpret())


def stochastic_quantize(x: jax.Array, key: jax.Array, bits: int, *,
                        clip: float = 1.0, stochastic: bool = True) -> jax.Array:
    """Quantize-dequantize through the kernel pair (f32 out)."""
    codes = stochastic_quantize_codes(x, key, bits, clip=clip, stochastic=stochastic)
    return _quant.dequantize_codes(codes, bits, clip=clip,
                                   interpret=_interpret())


def dequantize_codes(codes: jax.Array, bits: int, *, clip: float = 1.0) -> jax.Array:
    with _obs_trace.phase_span("pallas/dequantize_codes"):
        return _quant.dequantize_codes(codes, bits, clip=clip,
                                       interpret=_interpret())


def quantize_pack(x: jax.Array, key: jax.Array, bits: int, *,
                  clip: float = 1.0, lane_bits: int = 0,
                  stochastic: bool = True, u: jax.Array | None = None) -> jax.Array:
    """Fused quantize+pack through the kernel: x -> uint32 wire words.

    ``u`` supplies the rounding noise directly (e.g. per-leaf streams
    concatenated by the ring collective); otherwise it is drawn from ``key``
    exactly as the pure path's ``_uniform_like``.
    """
    if u is None:
        u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    with _obs_trace.phase_span("pallas/quantize_pack"):
        return _pack.quantize_pack(x, u, bits, clip=clip,
                                   lane_bits=lane_bits,
                                   stochastic=stochastic,
                                   interpret=_interpret())


def quantize_pack_chunk(x: jax.Array, key: jax.Array, bits: int, *,
                        clip: float = 1.0, lane_bits: int = 0,
                        stochastic: bool = True, num_chunks: int = 1,
                        bias: int | None = None,
                        u: jax.Array | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Fused collective front-end through the megakernel: quantize ``x``,
    split into ``num_chunks`` chunks and return (packed words (K, Wc),
    codes (K, C)) in one pass — the ring's (buf, acc) init at
    ``num_chunks=1`` and the rsag level-0 (chunks, hop-1 payload).  ``u``
    supplies the rounding noise directly (the per-leaf streams the
    collectives concatenate); otherwise drawn from ``key``."""
    if u is None:
        u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    with _obs_trace.phase_span("pallas/quantize_pack_chunk"):
        return _pack.quantize_pack_chunk(x, u, bits, clip=clip,
                                         lane_bits=lane_bits,
                                         stochastic=stochastic,
                                         num_chunks=num_chunks, bias=bias,
                                         interpret=_interpret())


def repack(packed: jax.Array, acc: jax.Array, bits: int, size: int, *,
           lane_bits: int = 0, sum_of: int = 1,
           bias: int | None = None) -> jax.Array:
    """Fused ring-hop accumulate: unpack wire words, add into the int32
    register tree (one VMEM pass).  ``bias`` overrides the sum_of·G un-bias
    (the rsag collective's lane-symmetric bias)."""
    with _obs_trace.phase_span("pallas/repack"):
        return _pack.repack(packed, acc, bits, size, lane_bits=lane_bits,
                            sum_of=sum_of, bias=bias,
                            interpret=_interpret())


def pack_sums(codes: jax.Array, bits: int, *, lane_bits: int = 0,
              sum_of: int = 1, bias: int | None = None) -> jax.Array:
    """Scatter-phase pack through the kernel: int32 partial-sum codes ->
    uint32 wire words at the hop's lane width (the rsag payload builder)."""
    with _obs_trace.phase_span("pallas/pack_sums"):
        return _pack.pack_sums(codes, bits, lane_bits=lane_bits,
                               sum_of=sum_of, bias=bias,
                               interpret=_interpret())


def unpack_dequantize(packed: jax.Array, bits: int, size: int, *,
                      clip: float = 1.0, lane_bits: int = 0,
                      sum_of: int = 1, bias: int | None = None) -> jax.Array:
    """Fused unpack+dequantize through the kernel: wire words -> flat f32.

    ``bias`` overrides the sum_of·G un-bias (the rsag all-gather's
    lane-symmetric bias) so finished chunks land as f32 directly — the
    fused scatter-store variant skipping the int32 round-trip."""
    with _obs_trace.phase_span("pallas/unpack_dequantize"):
        return _pack.unpack_dequantize(packed, bits, size, clip=clip,
                                       lane_bits=lane_bits, sum_of=sum_of,
                                       bias=bias, interpret=_interpret())


def qmatmul(x_q: jax.Array, w_q: jax.Array, sx, sw) -> jax.Array:
    with _obs_trace.phase_span("pallas/qmatmul"):
        return _qmm.qmatmul(x_q, w_q, jnp.float32(sx), jnp.float32(sw),
                            interpret=_interpret())


def masked_aggregate(updates: jax.Array, weights: jax.Array,
                     eps: float = 1e-12) -> jax.Array:
    with _obs_trace.phase_span("pallas/masked_aggregate"):
        return _agg.masked_aggregate(updates, weights, eps=eps,
                                     interpret=_interpret())
