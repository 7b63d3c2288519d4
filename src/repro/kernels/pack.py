"""Pallas TPU kernels: fused quantize-and-pack / unpack-and-dequantize /
mid-hop repack.

The packed wire format (see ``repro.core.quantization.pack_codes``) lays
biased n-bit codes planar into uint32 words: plane j of the flat code vector
occupies bit-lane [j·lane, (j+1)·lane) of word w.  The fused kernels do the
whole hot transform in one VMEM pass:

  quantize_pack:     f32 x, u  ->  scale, stochastic-round, clip, bias,
                                   shift-OR into uint32 words
  unpack_dequantize: uint32    ->  per-lane extract, un-bias, scale to f32
  repack:            uint32, i32 -> per-lane extract at the hop's sum width,
                                   un-bias, add into the int32 register tree
                                   (the ring collective's per-hop accumulate;
                                   the forwarded buffer is the incoming words
                                   unchanged, and level transitions re-pack
                                   the register tree at the next sum width)
  pack_sums:         i32       ->  bias partial-sum codes, shift-OR into
                                   uint32 words (the rsag collective's
                                   scatter-phase payload builder: the running
                                   chunk re-packs at each hop group's grown
                                   lane width before it re-enters the ring)
  quantize_pack_chunk: f32 x, u ->  the collective FRONT-END megakernel:
                                   quantize, split into num_chunks equal
                                   chunks, and emit BOTH the per-chunk
                                   packed uint32 words AND the per-chunk
                                   int32 codes in ONE pass — the ring's
                                   (buf, acc) init (num_chunks=1) and the
                                   rsag level-0 (chunks, hop-1 payload)
                                   without a second unpack/chunking pass

Blocks are (cpw, BLOCK_ROWS, 128) for the planar operands against
(BLOCK_ROWS, 128) word blocks — the planes of one word block ride in the
same grid step, so packing is a pure VPU shift/or with no cross-block
traffic.  Random bits stream in as an operand (threefry outside) exactly as
in ``kernels/quantize.py``; interpret mode keeps CPU parity with ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# (cpw, 128, 128) f32 x/u blocks stay <= 2 MB VMEM even at cpw=16 (bits=2).
BLOCK_ROWS = 128
LANES = 128


def _word_index(block_rows: int):
    """Flat word index of every element of this grid step's (block_rows,
    LANES) word block."""
    shape = (block_rows, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (pl.program_id(0) * block_rows + row) * LANES + col


def _quantize(x, u, *, gain: float, g: int, stochastic: bool):
    """f32 block -> int32 codes in [-g, g-1] (the clip interval is folded
    into ``gain`` by the caller)."""
    xq = jnp.clip(x.astype(jnp.float32), -1.0, 1.0) * gain
    rounded = jnp.floor(xq + u) if stochastic else jnp.round(xq)
    return jnp.clip(rounded, -g, g - 1).astype(jnp.int32)


def _quantize_pack_kernel(x_ref, u_ref, words_ref, *, gain: float, g: int,
                          lane: int, cpw: int, n: int, W: int,
                          stochastic: bool):
    w = _word_index(words_ref.shape[0])
    words = jnp.zeros(words_ref.shape, jnp.uint32)
    # the cpw planes are static: an unrolled modular shift-add per plane
    # (Mosaic has no unsigned reduction and no 1-D -> 3-D shape cast)
    for j in range(cpw):
        codes = _quantize(x_ref[j], u_ref[j], gain=gain, g=g,
                          stochastic=stochastic)
        valid = (w < W) & (j * W + w < n)                  # real elements only
        biased = jnp.where(valid, codes + g, 0).astype(jnp.uint32)
        words = words + (biased << jnp.uint32(j * lane))
    words_ref[...] = words


@functools.partial(jax.jit, static_argnames=("bits", "clip", "lane_bits",
                                             "stochastic", "interpret"))
def quantize_pack(x: jax.Array, u: jax.Array, bits: int, *, clip: float = 1.0,
                  lane_bits: int = 0, stochastic: bool = True,
                  interpret: bool) -> jax.Array:
    """Fused quantize+pack: f32 ``x`` with noise ``u`` -> uint32 words (W,).

    Bit-exact with ``pack_codes(quantize_codes(x, ·), ·)`` for every size
    (padding lanes are masked to 0, matching the pure path).
    """
    n = x.size
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    cpw = 32 // lane
    W = -(-n // cpw)
    per_block = BLOCK_ROWS * LANES
    W_pad = -(-W // per_block) * per_block
    R = W_pad // LANES

    def planar(a):
        flat = jnp.pad(a.reshape(-1).astype(jnp.float32), (0, cpw * W - n))
        planes = flat.reshape(cpw, W)
        return jnp.pad(planes, ((0, 0), (0, W_pad - W))).reshape(cpw, R, LANES)

    xf = planar(x) / clip
    uf = planar(u)

    gain = float(2 ** (bits - 1))
    g = int(2 ** (bits - 1))
    words = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, gain=gain, g=g, lane=lane,
                          cpw=cpw, n=n, W=W, stochastic=stochastic),
        grid=(R // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.uint32),
        interpret=interpret,
    )(xf, uf)
    return words.reshape(-1)[:W]


def _unpack_dequantize_kernel(words_ref, out_ref, *, lane: int, cpw: int,
                              bias: int, inv_gain: float):
    words = words_ref[...]                                  # (BR, LANES) u32
    mask = jnp.uint32(2 ** lane - 1)
    for j in range(cpw):
        lanes = (words >> jnp.uint32(j * lane)) & mask
        # modular uint32 un-bias (exact for biases up to the full lane width,
        # e.g. the rsag lane_bias 2^(lane-1) at lane 32)
        vals = (lanes - jnp.uint32(bias)).astype(jnp.int32)
        out_ref[j] = vals.astype(jnp.float32) * inv_gain


@functools.partial(jax.jit, static_argnames=("bits", "size", "clip",
                                             "lane_bits", "sum_of", "bias",
                                             "interpret"))
def unpack_dequantize(packed: jax.Array, bits: int, size: int, *,
                      clip: float = 1.0, lane_bits: int = 0, sum_of: int = 1,
                      bias: int | None = None,
                      interpret: bool) -> jax.Array:
    """Fused unpack+dequantize: uint32 words -> flat f32 of length ``size``.

    ``sum_of`` un-biases an aggregated buffer (psum of ``sum_of`` packed
    shards adds one +G per summand per lane); ``bias`` overrides the
    sum_of·G un-bias with an explicit value (the rsag collective's
    lane-symmetric ``quantization.lane_bias`` — what lets its all-gather
    store land dequantized f32 chunks directly, skipping the int32
    round-trip on the last level).
    """
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    cpw = 32 // lane
    W = packed.size
    per_block = BLOCK_ROWS * LANES
    W_pad = -(-W // per_block) * per_block
    R = W_pad // LANES
    words = jnp.pad(packed.reshape(-1), (0, W_pad - W)).reshape(R, LANES)

    g = int(2 ** (bits - 1))
    inv_gain = clip / float(2 ** (bits - 1))
    planes = pl.pallas_call(
        functools.partial(_unpack_dequantize_kernel, lane=lane, cpw=cpw,
                          bias=g * int(sum_of) if bias is None else int(bias),
                          inv_gain=inv_gain),
        grid=(R // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((cpw, R, LANES), jnp.float32),
        interpret=interpret,
    )(words)
    return planes.reshape(cpw, W_pad)[:, :W].reshape(-1)[: int(size)]


def _repack_kernel(words_ref, acc_ref, out_ref, *, lane: int, cpw: int,
                   bias: int, n: int, W: int):
    words = words_ref[...]                                  # (BR, LANES) u32
    w = _word_index(words.shape[0])
    mask = jnp.uint32(2 ** lane - 1)
    for j in range(cpw):
        lanes = (words >> jnp.uint32(j * lane)) & mask
        valid = (w < W) & (j * W + w < n)
        # modular uint32 un-bias (exact for biases up to the full lane width)
        vals = (lanes - jnp.uint32(bias)).astype(jnp.int32)
        out_ref[j] = acc_ref[j] + jnp.where(valid, vals, 0)


@functools.partial(jax.jit, static_argnames=("bits", "size", "lane_bits",
                                             "sum_of", "bias", "interpret"))
def repack(packed: jax.Array, acc: jax.Array, bits: int, size: int, *,
           lane_bits: int = 0, sum_of: int = 1, bias: int | None = None,
           interpret: bool) -> jax.Array:
    """Fused mid-hop accumulate of the ring collective: unpack ``packed``
    (partial sums of ``sum_of`` codes, biased by sum_of·G per lane at the
    hop's ``lane_bits`` width) and add it into the flat int32 register tree
    ``acc`` — one VMEM pass instead of unpack-materialize-add.  ``bias``
    overrides the sum_of·G un-bias (the rsag collective's lane-symmetric
    ``lane_bias`` scheme).

    Bit-exact with ``acc + unpack_codes(packed, ·, sum_of=·, bias=·)``.
    """
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    cpw = 32 // lane
    n = int(size)
    W = packed.size
    per_block = BLOCK_ROWS * LANES
    W_pad = -(-W // per_block) * per_block
    R = W_pad // LANES
    words = jnp.pad(packed.reshape(-1), (0, W_pad - W)).reshape(R, LANES)
    # acc in the planar-of-wire geometry so word and register blocks align
    acc_planes = jnp.pad(acc.reshape(-1).astype(jnp.int32),
                         (0, cpw * W - n))
    acc_planes = jnp.pad(acc_planes.reshape(cpw, W),
                         ((0, 0), (0, W_pad - W))).reshape(cpw, R, LANES)

    g = int(2 ** (bits - 1))
    planes = pl.pallas_call(
        functools.partial(_repack_kernel, lane=lane, cpw=cpw,
                          bias=g * int(sum_of) if bias is None else int(bias),
                          n=n, W=W),
        grid=(R // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((cpw, R, LANES), jnp.int32),
        # the accumulate is in-place: the planar acc operand donates its
        # buffer to the output (the scan carry never copies)
        input_output_aliases={1: 0},
        interpret=interpret,
    )(words, acc_planes)
    return planes.reshape(cpw, W_pad)[:, :W].reshape(-1)[:n]


def _quantize_pack_chunk_kernel(x_ref, u_ref, words_ref, codes_ref, *,
                                gain: float, g: int, lane: int, K: int,
                                cpw: int, C: int, Wc: int, bias: int,
                                stochastic: bool):
    # all chunks ride in the SAME grid step (leading dim = K·cpw planes):
    # one row-stripe grid keeps the step count O(R/br) instead of O(K·R/br)
    w = _word_index(words_ref.shape[1])
    for k in range(K):
        words = jnp.zeros(words_ref.shape[1:], jnp.uint32)
        for j in range(cpw):
            p = k * cpw + j
            codes = _quantize(x_ref[p], u_ref[p], gain=gain, g=g,
                              stochastic=stochastic)
            valid = (w < Wc) & (j * Wc + w < C)        # real elements only
            codes_ref[p] = jnp.where(valid, codes, 0)
            # modular uint32 biasing: exact even for the lane-symmetric
            # 2^(lane-1) bias at lane 32 (an int32 add would overflow)
            biased = jnp.where(valid,
                               codes.astype(jnp.uint32) + jnp.uint32(bias),
                               jnp.uint32(0))
            words = words + (biased << jnp.uint32(j * lane))
        words_ref[k] = words


#: scoped-VMEM budget for the chunk megakernel's pipelined blocks (both
#: inputs, both outputs, each double-buffered) — under v5e's 16 MiB default
#: scoped limit with room left for the kernel body's per-plane temporaries
_CHUNK_VMEM_BUDGET = 10 * 2 ** 20


def _chunk_stripe_rows(K: int, cpw: int, Wc: int) -> int:
    """Row stripe of the chunk megakernel: the largest power-of-two
    multiple of 8 rows whose double-buffered blocks — x and u
    (K·cpw planes f32), codes (K·cpw planes i32) and words (K planes u32)
    — fit ``_CHUNK_VMEM_BUDGET``, and no wider than one chunk needs."""
    bytes_per_row = 2 * LANES * 4 * (3 * K * cpw + K)
    br = 8
    while 2 * br * bytes_per_row <= _CHUNK_VMEM_BUDGET and br * LANES < Wc:
        br *= 2
    return br


@functools.partial(jax.jit, static_argnames=("bits", "clip", "lane_bits",
                                             "stochastic", "num_chunks",
                                             "bias", "interpret"))
def quantize_pack_chunk(x: jax.Array, u: jax.Array, bits: int, *,
                        clip: float = 1.0, lane_bits: int = 0,
                        stochastic: bool = True, num_chunks: int = 1,
                        bias: int | None = None,
                        interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused collective front-end: quantize ``x``, split into ``num_chunks``
    chunks of C = ceil(n/num_chunks), and emit per chunk BOTH the packed
    uint32 wire words (num_chunks, ceil(C/cpw)) and the int32 codes
    (num_chunks, C) — one VMEM pass instead of quantize + pack + XLA
    pad/reshape chunking.

    The chunk-pad tail (n..num_chunks·C) quantizes a zero input with zero
    noise to the REAL zero code (floor(0+0) = 0), matching the sequential
    path's ``jnp.pad`` of the code vector, so pad elements are biased on
    the wire exactly like the pure path; word padding past C stays raw 0.
    ``bias`` overrides the native +G code bias (the rsag level-0 payload's
    lane-symmetric ``lane_bias`` — identical to G at the native lane).

    Bit-exact with ``ref.quantize_pack_chunk_ref``.
    """
    n = x.size
    K = int(num_chunks)
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    cpw = 32 // lane
    C = -(-n // K)
    Wc = -(-C // cpw)
    br = _chunk_stripe_rows(K, cpw, Wc)
    per_block = br * LANES
    Wc_pad = -(-Wc // per_block) * per_block
    R = Wc_pad // LANES

    def planar(a):
        flat = jnp.pad(a.reshape(-1).astype(jnp.float32), (0, K * C - n))
        ch = jnp.pad(flat.reshape(K, C), ((0, 0), (0, cpw * Wc - C)))
        return jnp.pad(ch.reshape(K, cpw, Wc),
                       ((0, 0), (0, 0), (0, Wc_pad - Wc))
                       ).reshape(K * cpw, R, LANES)

    xf = planar(x) / clip
    uf = planar(u)

    gain = float(2 ** (bits - 1))
    g = int(2 ** (bits - 1))
    words, codes = pl.pallas_call(
        functools.partial(_quantize_pack_chunk_kernel, gain=gain, g=g,
                          lane=lane, K=K, cpw=cpw, C=C, Wc=Wc,
                          bias=g if bias is None else int(bias),
                          stochastic=stochastic),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((K * cpw, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((K * cpw, br, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((K, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((K * cpw, br, LANES), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, R, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((K * cpw, R, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(xf, uf)
    words = words.reshape(K, -1)[:, :Wc]
    codes = codes.reshape(K, cpw, Wc_pad)[:, :, :Wc].reshape(K, -1)[:, :C]
    return words, codes


def _pack_sums_kernel(codes_ref, words_ref, *, bias: int, lane: int, cpw: int,
                      n: int, W: int):
    w = _word_index(words_ref.shape[0])
    words = jnp.zeros(words_ref.shape, jnp.uint32)
    for j in range(cpw):
        valid = (w < W) & (j * W + w < n)                  # real elements only
        biased = jnp.where(valid,
                           codes_ref[j].astype(jnp.uint32) + jnp.uint32(bias),
                           jnp.uint32(0))
        words = words + (biased << jnp.uint32(j * lane))
    words_ref[...] = words


@functools.partial(jax.jit, static_argnames=("bits", "lane_bits", "sum_of",
                                             "bias", "interpret"))
def pack_sums(codes: jax.Array, bits: int, *, lane_bits: int = 0,
              sum_of: int = 1, bias: int | None = None,
              interpret: bool) -> jax.Array:
    """Scatter-phase pack: int32 PARTIAL-SUM codes -> uint32 wire words.

    The rsag collective's outgoing payload builder: the running chunk
    (partial sums of ``sum_of`` codes) is biased and bit-packed planar at
    the hop's ``lane_bits`` width in one VMEM pass — the pack half of
    ``quantize_pack`` without the quantizer (the codes were quantized once,
    before the first hop).  ``bias`` overrides the sum_of·G default (rsag
    uses the lane-symmetric ``quantization.lane_bias``).

    Bit-exact with ``pack_codes(codes, bits, lane_bits=·, sum_of=·, bias=·)``
    for every size (padding lanes masked to raw 0, matching the pure path).
    """
    n = codes.size
    lane = lane_bits or bits
    if lane > 32:
        raise ValueError(f"lane width {lane} exceeds the 32-bit container")
    cpw = 32 // lane
    W = -(-n // cpw)
    per_block = BLOCK_ROWS * LANES
    W_pad = -(-W // per_block) * per_block
    R = W_pad // LANES
    flat = jnp.pad(codes.reshape(-1).astype(jnp.int32), (0, cpw * W - n))
    planes = jnp.pad(flat.reshape(cpw, W),
                     ((0, 0), (0, W_pad - W))).reshape(cpw, R, LANES)

    g = int(2 ** (bits - 1))
    words = pl.pallas_call(
        functools.partial(_pack_sums_kernel,
                          bias=g * int(sum_of) if bias is None else int(bias),
                          lane=lane, cpw=cpw, n=n, W=W),
        grid=(R // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((cpw, BLOCK_ROWS, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.uint32),
        interpret=interpret,
    )(planes)
    return words.reshape(-1)[:W]
