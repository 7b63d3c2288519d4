"""Stochastic fixed-point quantization (paper §II-A/B).

The paper's three-step procedure:
  1. scale up:   w_Q = clip(w, [-1,1]) * G,  G = 2^(n-1)
  2. stochastic rounding:  floor(w_Q) w.p. 1-frac, floor(w_Q)+1 w.p. frac
  3. scale down: w_r = R(w_Q) / G

Stochastic rounding is unbiased: E[quantize(w)] == clip(w).  Integer codes live
in [-G, G] (the top code G is reachable only by rounding up from values just
below +1; we clip codes to G-1 ... actually to keep the signed n-bit range
[-G, G-1] exactly representable we clip the *input* to (G-1)/G when strict
n-bit containment is requested).

All functions are pure jnp and jit/vmap/pjit friendly; ``use_pallas`` routes
through the Pallas TPU kernel (validated in interpret mode on CPU).

Beyond the paper's math, this module owns the *wire format*: ``pack_codes``
/ ``unpack_codes`` lay n-bit codes into dense uint32 words (32//n codes per
word, planar bit-lanes) so the simulated collective payload matches the
paper's §II-D2 ``payload_bits`` accounting instead of shipping one int16/32
container per parameter.  See ``packed_payload_bits`` /
``ring_payload_bits`` / ``rsag_payload_bits`` for the exact wire sizes of
the one-shot guard-lane psum, the per-hop native-width ring, and the
reduce-scatter+all-gather with growing lanes, and ``repro.kernels.pack``
for the fused Pallas quantize-and-pack / unpack-and-dequantize / repack /
pack-sums kernels.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import QuantConfig

PyTree = Any


def _uniform_like(key: jax.Array, x: jax.Array) -> jax.Array:
    return jax.random.uniform(key, x.shape, dtype=jnp.float32)


def quantize_codes(x: jax.Array, key: jax.Array, bits: int, *,
                   clip: float = 1.0, stochastic: bool = True) -> jax.Array:
    """Return integer codes (int32) in [-(G), G] with G = 2^(bits-1)·clip⁻¹-scaled.

    Codes are produced from x clipped to [-clip, clip]; the effective step is
    clip / G so the dequantized grid spans the clip interval.
    """
    if bits <= 0:
        raise ValueError("bits must be positive for quantization")
    gain = (2.0 ** (bits - 1)) / clip
    xq = jnp.clip(x.astype(jnp.float32), -clip, clip) * gain
    if stochastic:
        u = _uniform_like(key, xq)
        codes = jnp.floor(xq + u)
    else:
        codes = jnp.round(xq)
    # keep codes in the signed n-bit container range [-G, G-1]... the paper's
    # [-1, 1) convention; +G (from x == +clip) saturates to G-1.
    g = int(2 ** (bits - 1))
    return jnp.clip(codes, -g, g - 1).astype(jnp.int32)


def dequantize_codes(codes: jax.Array, bits: int, *, clip: float = 1.0,
                     dtype=jnp.float32) -> jax.Array:
    gain = (2.0 ** (bits - 1)) / clip
    return (codes.astype(jnp.float32) / gain).astype(dtype)


def quantize(x: jax.Array, key: jax.Array, cfg: QuantConfig) -> jax.Array:
    """Quantize-dequantize (the value actually used for compute/transmission)."""
    if not cfg.enabled:
        return x
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.stochastic_quantize(x, key, cfg.bits, clip=cfg.clip,
                                        stochastic=cfg.stochastic).astype(x.dtype)
    codes = quantize_codes(x, key, cfg.bits, clip=cfg.clip, stochastic=cfg.stochastic)
    return dequantize_codes(codes, cfg.bits, clip=cfg.clip, dtype=x.dtype)


def quantize_tree(tree: PyTree, key: jax.Array, cfg: QuantConfig) -> PyTree:
    """Quantize every array leaf with an independent PRNG stream."""
    if not cfg.enabled:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [quantize(leaf, k, cfg) for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def quantize_tree_codes(tree: PyTree, key: jax.Array, cfg: QuantConfig) -> PyTree:
    """Integer codes for every leaf (what actually crosses the wire)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [quantize_codes(leaf, k, cfg.bits, clip=cfg.clip, stochastic=cfg.stochastic)
           for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_tree_codes(codes: PyTree, cfg: QuantConfig, dtype=jnp.float32) -> PyTree:
    return jax.tree_util.tree_map(
        lambda c: dequantize_codes(c, cfg.bits, clip=cfg.clip, dtype=dtype), codes)


# ---------------------------------------------------------------------------
# Bit packing: n-bit codes -> dense uint32 words (the wire format).
#
# Codes in [-G, G-1] are biased to unsigned [0, 2^bits-1] and laid out
# *planar*: the flat code vector (padded to cpw·W, W = ceil(n/cpw)) is viewed
# as (cpw, W) planes and plane j occupies bit-lane [j·lane, (j+1)·lane) of
# word w.  ``lane_bits`` defaults to ``bits`` (pure storage packing); an
# aggregating collective passes ``bits + ceil(log2(num_shards))`` so that a
# psum of packed words accumulates every bit-lane without cross-lane carries
# — the per-bit-lane partial-sum trick that keeps the packed dtype on the
# wire (see the "packed" reducer in aggregation.aggregate).
# ---------------------------------------------------------------------------


def packed_lane_bits(bits: int, num_shards: int = 1) -> int:
    """Bit-lane width so a sum over ``num_shards`` biased codes cannot carry."""
    guard = math.ceil(math.log2(num_shards)) if num_shards > 1 else 0
    return bits + guard


def lane_bias(lane: int) -> int:
    """Mid-lane bias 2^(lane-1) — the lane-symmetric alternative to the
    default ``sum_of``·G bias.  A partial sum of m codes at the carry-free
    lane ``packed_lane_bits(bits, m)`` always fits around this bias
    (m·G <= 2^(lane-1)), so every hop of an equal-lane group can share ONE
    static bias regardless of how many codes its payload has accumulated —
    what lets the rsag collective run a lane group as a single ``lax.scan``.
    """
    return 1 << (int(lane) - 1)


def codes_per_word(bits: int, *, lane_bits: int = 0) -> int:
    """How many codes one uint32 word holds at the given lane width."""
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    return 32 // lane


def packed_words(n: int, bits: int, *, lane_bits: int = 0) -> int:
    """Number of uint32 words packing ``n`` codes."""
    return -(-int(n) // codes_per_word(bits, lane_bits=lane_bits))


def flat_buffer(pieces: Sequence[jax.Array],
                size: int | None = None) -> jax.Array:
    """The pieces flattened end to end into one vector of ``size`` elements
    (default: their total; a larger size leaves a zero tail).

    Equal bit for bit to ``jnp.pad(jnp.concatenate(...), ...)``, but each
    piece, and the zero tail, is written with ``lax.dynamic_update_slice``
    at a static offset into an uninitialised buffer (``lax.empty``, which
    the TPU compiler allocates without writing, as it does for a
    concatenate).  The TPU compiler lowers a concatenate of these sizes to
    a chain of buffer writes that carry no ``op_name``; these writes keep
    the caller's ``phase_span``, so the device time of the wire's flat
    buffers is attributed to its phase.
    """
    flat = [p.reshape(-1) for p in pieces]
    total = sum(p.size for p in flat)
    size = total if size is None else int(size)
    if size < total:
        raise ValueError(f"flat_buffer: {total} elements exceed size {size}")
    dtype = jnp.result_type(*flat) if flat else jnp.float32
    flat = [p.astype(dtype) for p in flat if p.size]
    if size > total:
        flat.append(jnp.zeros((size - total,), dtype))
    buf = jax.lax.empty((size,), dtype)
    offset = 0
    for p in flat:
        buf = jax.lax.dynamic_update_slice(buf, p, (offset,))
        offset += p.size
    return buf


def pack_codes(codes: jax.Array, bits: int, *, lane_bits: int = 0,
               sum_of: int = 1, bias: int | None = None) -> jax.Array:
    """Pack int32 codes in [-G, G-1] into a flat uint32 word vector.

    ``sum_of`` packs PARTIAL SUMS of that many codes (values in
    [-m·G, m·(G-1)], biased by m·G) — the ring collective's inter-level
    repack; the lane must be at least ``packed_lane_bits(bits, sum_of)``.
    ``bias`` overrides the default ``sum_of``·G bias with an explicit value
    (the rsag collective biases every lane-L payload by ``lane_bias(L)``
    so a whole equal-lane hop group shares one static bias).

    Padding lanes (beyond ``codes.size``) hold 0 — NOT the biased zero code —
    so unpack can distinguish them and packed buffers compare bit-exactly
    across implementations (the Pallas kernel masks identically).
    """
    lane = lane_bits or bits
    cpw = codes_per_word(bits, lane_bits=lane)
    b = int(2 ** (bits - 1)) * int(sum_of) if bias is None else int(bias)
    n = codes.size
    W = packed_words(n, bits, lane_bits=lane)
    # modular uint32 add: exact for every lane width up to the full 32 bits
    # (an int32 add would overflow for biases >= 2^31)
    biased = codes.reshape(-1).astype(jnp.uint32) + jnp.uint32(b)
    biased = flat_buffer([biased], cpw * W).reshape(cpw, W)
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * lane)[:, None]
    return jnp.sum(biased << shifts, axis=0, dtype=jnp.uint32)


def unpack_codes(packed: jax.Array, bits: int, size: int, *,
                 lane_bits: int = 0, sum_of: int = 1,
                 bias: int | None = None) -> jax.Array:
    """Inverse of :func:`pack_codes`: uint32 words -> int32 codes (flat).

    ``sum_of`` = number of packed buffers summed into ``packed`` (each summand
    contributes one +G bias per lane); 1 for a plain round-trip, the shard
    count when unpacking an aggregated psum of packed words.  ``bias``
    overrides the ``sum_of``·G un-bias (must match the packing side).
    """
    lane = lane_bits or bits
    cpw = codes_per_word(bits, lane_bits=lane)
    b = int(2 ** (bits - 1)) * int(sum_of) if bias is None else int(bias)
    W = packed.size
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * lane)[:, None]
    mask = jnp.uint32(2 ** lane - 1)
    lanes = (packed.reshape(1, W) >> shifts) & mask            # (cpw, W)
    flat = lanes.reshape(-1)[: int(size)]
    return (flat - jnp.uint32(b)).astype(jnp.int32)


def pack_tree_codes(codes: PyTree, cfg: QuantConfig, *,
                    lane_bits: int = 0) -> PyTree:
    """Pack every integer-code leaf (what crosses the packed wire)."""
    return jax.tree_util.tree_map(
        lambda c: pack_codes(c, cfg.bits, lane_bits=lane_bits), codes)


def packed_payload_bits(num_params: int, bits: int, *,
                        num_shards: int = 1) -> int:
    """Actual wire bits of the packed uplink: 32 · ceil(d / cpw).

    Approaches the ideal ``payload_bits`` d·n as d grows (exact when
    lane_bits == bits and cpw | d); the guard lanes for an aggregating psum
    add the ceil(log2(K)) per-lane overhead.
    """
    lane = packed_lane_bits(bits, num_shards)
    return 32 * packed_words(num_params, bits, lane_bits=lane)


def ring_payload_bits(num_params: int, bits: int,
                      axis_sizes: Sequence[int]) -> int:
    """Per-device wire bits of the ring collective, summed over every hop.

    The ring circulates RAW codes packed at the native ``bits`` lane (no
    guard bits): level ``l`` over a cohort axis of size K_l ships, on each
    of its K_l - 1 hops, partial sums of ``m_l`` codes packed at lane
    ``packed_lane_bits(bits, m_l)`` where m_l is the product of the
    preceding axis sizes (m_0 = 1 -> native width).  Single-axis cohorts
    therefore pay ~(K-1)/... hops of d·n bits each — 0.75x the guard-lane
    psum at K=2, n=8 — but the cost grows linearly in K, so the one-shot
    packed psum wins back for large single-axis cohorts (see
    ``aggregation.wire_bits_per_param`` for the mode-selection math).
    """
    total = 0
    m = 1
    for k in axis_sizes:
        k = int(k)
        if k <= 1:
            continue
        lane = packed_lane_bits(bits, m)
        total += (k - 1) * 32 * packed_words(num_params, bits, lane_bits=lane)
        m *= k
    return total


def rsag_payload_bits(num_params: int, bits: int,
                      axis_sizes: Sequence[int]) -> int:
    """Per-device wire bits of the reduce-scatter + all-gather collective.

    Level ``l`` (cohort axis size K_l, entering partial-sum multiplicity
    m_l = product of preceding axis sizes) chunks the flat code vector into
    K_l pieces of C = ceil(d / K_l) codes.  The reduce-scatter phase ships
    one chunk per hop h = 1..K_l-1 at the GROWING lane
    ``packed_lane_bits(bits, m_l·h)`` (hop h carries partial sums of m_l·h
    codes); the all-gather phase ships K_l-1 finished chunks at the final
    lane ``packed_lane_bits(bits, m_l·K_l)``.  Total ~ 2·d·(n + ⌈log2 K⌉)
    regardless of K — the large-K cap the per-hop ring (d·n·(K-1)) lacks.
    """
    total = 0
    m = 1
    for k in axis_sizes:
        k = int(k)
        if k <= 1:
            continue
        C = -(-int(num_params) // k)
        for h in range(1, k):
            lane = packed_lane_bits(bits, m * h)
            total += 32 * packed_words(C, bits, lane_bits=lane)
        lane_k = packed_lane_bits(bits, m * k)
        total += (k - 1) * 32 * packed_words(C, bits, lane_bits=lane_k)
        m *= k
    return total


def quantization_variance_bound(bits: int, clip: float = 1.0) -> float:
    """Per-element variance bound of stochastic rounding: step²/4, step = clip/2^(n-1)."""
    step = clip / (2.0 ** (bits - 1))
    return step * step / 4.0


def payload_bits(num_params: int, bits: int) -> int:
    """Uplink payload d_n^u = d^u * n (paper §II-D2)."""
    return int(num_params) * int(bits)


# ---------------------------------------------------------------------------
# Straight-through estimator for quantization-aware local training (QNN).
# Forward: quantized weights; backward: identity (plus clip mask).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fake_quant_ste(x: jax.Array, key: jax.Array, bits: int, clip: float,
                   stochastic: bool) -> jax.Array:
    codes = quantize_codes(x, key, bits, clip=clip, stochastic=stochastic)
    return dequantize_codes(codes, bits, clip=clip, dtype=x.dtype)


def _fq_fwd(x, key, bits, clip, stochastic):
    y = fake_quant_ste(x, key, bits, clip, stochastic)
    return y, (x,)


def _fq_bwd(bits, clip, stochastic, res, g):
    (x,) = res
    mask = (jnp.abs(x) <= clip).astype(g.dtype)  # clipped STE
    return (g * mask, None)


fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)


def fake_quant_params(params: PyTree, key: jax.Array, cfg: QuantConfig) -> PyTree:
    """STE fake-quantization of a parameter tree (used inside the local loss)."""
    if not (cfg.enabled and cfg.quantize_training):
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    out = [fake_quant_ste(leaf, k, cfg.bits, cfg.clip, cfg.stochastic)
           for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)
