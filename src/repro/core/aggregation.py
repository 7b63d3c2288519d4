"""Error-aware update aggregation (paper §II-C, eq. 5/6).

Pure forms (used by the MNIST simulator and tests):
  naive_aggregate    — eq. 5: w + (1/K) Σ Δ_k (drops become silent zeros)
  error_aware_aggregate — eq. 6: w + Σ α_k λ_k Δ_k / Σ α_k λ_k

Collective forms (used inside the shard_map'd distributed FL round, one
client cohort per ``data`` mesh shard) are organised around a **WirePlan**:
a plan object built ONCE from ``(collective, QuantConfig, mesh axes,
axis sizes)`` by :func:`make_wire_plan` that resolves the "auto" cost-model
mode, applies the degenerate fallbacks, and owns the wire accounting; the
shared flatten→scale→quantize front-end and dequantize→renormalize→
unflatten back-end live in :func:`aggregate`, and each wire format reduces
to one small code-sum strategy in ``_REDUCERS``.  Six modes, selected by
``QuantConfig.wire_format`` / ``make_fl_round(collective=...)``:

  "paper" / "f32"
      Paper-faithful: quantize-dequantize locally, f32 psum of the weighted
      survivors.  Wire = 32 bits/param, regardless of ``quant.bits`` — the
      §II-D2 ``payload_bits`` d·n accounting is *simulated*, not realised.

  "int"
      Beyond-paper: the integer codes cross the wire in the smallest int
      container (int8/16/32) that can hold the shard sum.  Wire = 8-32
      bits/param — closer to d·n, but still one container per parameter.

  "packed"
      The wire matches the paper's payload accounting: codes are biased
      unsigned and bit-packed into dense uint32 words with a
      ceil(log2(K))-bit guard per lane, so ONE u32 psum accumulates every
      bit-lane without cross-lane carries (per-bit-lane partial sums).
      Wire = 32/⌊32/(n+⌈log2 K⌉)⌋ bits/param — e.g. 10.7 at n=8, K=2
      vs 16 for "int" and 32 for "paper".  Numerically identical to "int"
      (same codes, same exact integer sum).

  "ring"
      The guard bits go away: the whole code tree is concatenated, packed
      at the NATIVE n-bit lane, and circulated around the cohort ring with
      ``lax.ppermute`` — each hop unpacks the incoming buffer and
      accumulates it into an int32 register tree, so the wire carries
      exactly n bits/param per hop.  Multi-axis cohorts run nested rings,
      re-packing the partial sums at n+⌈log2 m⌉ between levels.  Total
      wire = Σ_l (K_l−1)·32/⌊32/(n+⌈log2 m_l⌉)⌋ bits/param — e.g. 8 at
      n=8, K=2 (0.75x "packed") — best for the small cohort counts of the
      hierarchical-FL meshes, but the cost grows with K−1 hops of the FULL
      vector.  Numerically identical to "int"/"packed".

  "rsag"
      True reduce-scatter + all-gather: the flat code vector splits into K
      chunks of ceil(d/K); the scatter phase ships ONE chunk per hop at a
      *growing* lane width (hop h carries partial sums of h codes in
      n+⌈log2 h⌉-bit lanes), the gather phase redistributes the finished
      chunks at the final n+⌈log2 K⌉ lane.  Total wire ≈
      2·d·(n+⌈log2 K⌉)/K·(K−1) bits — capped near 2·d·(n+⌈log2 K⌉)
      regardless of K, the large-K regime where the per-hop ring loses.
      Equal-lane hop groups run as one ``lax.scan`` (payloads share a
      lane-symmetric ``lane_bias`` so the pack/unpack constants stay
      static).  Numerically identical to "int"/"packed"/"ring".

  "auto"
      Not a wire format: resolved AT TRACE TIME by :func:`resolve_auto` to
      the byte-minimal concrete mode for the current (bits, axis sizes)
      via :func:`wire_bits_per_param` — ring for small cohorts, packed/rsag
      as K grows (e.g. ring on the 2x4 debug mesh, packed at 16x16).

All modes renormalize by psum(α·λ) (eq. 6) and degrade gracefully: with
quantization disabled (bits=0) or the uplink unquantized
(quantize_uplink=False) every mode falls back to the f32 psum, and
"packed"/"ring"/"rsag" fall back to "int" when the lane would exceed the
u32 container (huge bits x shards) — ``WirePlan.effective`` /
``effective_wire_format`` report the format that actually hits the wire so
telemetry/energy charge the bytes really sent (per phase via
``wire_phase_bits_per_param``).  When ``QuantConfig.use_pallas`` is set,
the hot transforms run through the fused Pallas kernels in
``repro.kernels.pack`` (interpret mode on CPU), bit-exact with the
pure-jnp path: quantize_pack/unpack_dequantize in the packed psum,
the ``quantize_pack_chunk`` megakernel front + the mid-hop ``repack``
accumulate in the ring, and the megakernel + ``pack_sums`` + ``repack``
(lane-bias variants) in the rsag phases.

``QuantConfig.pipeline_hops`` (default True) double-buffers the hop
loops: the ring scan and the rsag all-gather issue hop h+1's
``lax.ppermute`` before hop h's repack/accumulate lands (see the schedule
diagram on :func:`_reduce_ring`), and the quantize→pack→chunk front-end
fuses into ONE ``quantize_pack_chunk`` pass under ``use_pallas``.  Same
hops, same accumulation order — bit-identical to the sequential
schedule; False restores the sequential/unfused path for A/B timing.

Measured wall-clock per aggregate (d = 421 642, bits = 8, CPU interpret;
``benchmarks/BENCH_collective_modes.json`` — TRENDS portable, absolute
µs machine-specific; gated by ``benchmarks/run.py --check``):

  mode    wire bits/param      wall-clock pipelined vs sequential
          K=2      K=16        K=2 (auto=ring)    K=16 (auto=packed)
  packed  10.67    16.0        ~25 ms (0.94x, band) ~269 ms (0.98x, band)
  ring     8.0    120.0        ~21 ms (1.64x)     ~1188 ms (1.02x)
  rsag     9.33    28.5        ~19 ms (1.52x)      ~200 ms (1.18x)

The hop modes win from the fused front-end (3 passes → 1 at K=2) plus
the overlapped schedule; packed is hop-free, so the knob must not move
it (the --check invariance band asserts exactly that).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import COLLECTIVE_CHOICES, QuantConfig
from repro.core import quantization as quant
from repro.obs import trace as obs_trace

PyTree = Any
EPS = 1e-12

#: concrete wire formats ("auto" is a resolution rule, not a format)
COLLECTIVES = tuple(m for m in COLLECTIVE_CHOICES if m != "auto")
#: candidate order for "auto" (first wins wire-bit ties)
AUTO_ORDER = ("ring", "rsag", "packed", "int")


# ---------------------------------------------------------------------------
# pure (simulator) forms: updates stacked on a leading K axis
# ---------------------------------------------------------------------------

def naive_aggregate(w: PyTree, deltas: PyTree, lambdas: jnp.ndarray) -> PyTree:
    """eq. 5 with drops zeroed: w + (1/K) Σ λ_k Δ_k."""
    K = lambdas.shape[0]

    def agg(wl, dl):
        lam = lambdas.reshape((K,) + (1,) * (dl.ndim - 1))
        return wl + jnp.sum(dl * lam, axis=0).astype(wl.dtype) / K

    return jax.tree_util.tree_map(agg, w, deltas)


def error_aware_aggregate(w: PyTree, deltas: PyTree, alphas: jnp.ndarray,
                          lambdas: jnp.ndarray) -> PyTree:
    """eq. 6: surviving updates renormalized by the surviving data mass."""
    K = lambdas.shape[0]
    wts = alphas * lambdas
    den = jnp.maximum(jnp.sum(wts), EPS)

    def agg(wl, dl):
        ww = wts.reshape((K,) + (1,) * (dl.ndim - 1))
        return wl + (jnp.sum(dl * ww, axis=0) / den).astype(wl.dtype)

    return jax.tree_util.tree_map(agg, w, deltas)


# ---------------------------------------------------------------------------
# wire accounting: what actually hits the wire per mode (incl. fallbacks)
# ---------------------------------------------------------------------------

def _int_container(bits: int, num_shards: int):
    """Smallest signed int dtype holding Σ over shards of ±2^(bits-1) codes."""
    need = bits - 1 + math.ceil(math.log2(max(num_shards, 2))) + 1
    if need <= 7:
        return jnp.int8
    if need <= 15:
        return jnp.int16
    return jnp.int32


def resolve_auto(qcfg: QuantConfig, axis_sizes: Sequence[int]) -> str:
    """The byte-minimal concrete mode for (bits, axis_sizes) — what the
    "auto" collective lowers to.

    Candidates are compared by :func:`wire_bits_per_param` (the honest
    per-device total including every hop and the degenerate fallbacks);
    ties go to the earlier entry of ``AUTO_ORDER``.  The winner is then
    collapsed through :func:`effective_wire_format` so a pick whose lane
    would overflow reports the int container it actually ships ("auto"
    never resolves to a mode that silently falls back).
    """
    axis_sizes = tuple(int(s) for s in axis_sizes)
    if not (qcfg.enabled and qcfg.quantize_uplink):
        return "paper"
    best = min(AUTO_ORDER,
               key=lambda m: wire_bits_per_param(m, qcfg, axis_sizes))
    num_shards = 1
    for s in axis_sizes:
        num_shards *= s
    return effective_wire_format(best, qcfg, num_shards,
                                 axis_sizes=axis_sizes)


def effective_wire_format(collective: str, qcfg: QuantConfig,
                          num_shards: int, *,
                          axis_sizes: Sequence[int] | None = None) -> str:
    """The format that actually crosses the wire after degenerate fallbacks.

    "int"/"packed"/"ring"/"rsag" degrade to "paper" (f32 psum) when the
    uplink is not quantized, and "packed"/"ring"/"rsag" degrade to "int"
    when the psum lane / register tree would overflow its 32-bit container.
    "auto" is first resolved to its concrete pick (``axis_sizes`` defaults
    to the single-axis ``(num_shards,)`` cohort).  Telemetry and energy
    accounting must charge THIS format's bytes, not the requested one
    (otherwise the lane>32 fallback silently under-reports the wire).
    """
    if collective == "auto":
        collective = resolve_auto(
            qcfg, axis_sizes if axis_sizes is not None else (num_shards,))
    if collective not in COLLECTIVES:
        raise ValueError(f"unknown collective {collective!r}")
    if collective == "paper":
        return "paper"
    if not (qcfg.enabled and qcfg.quantize_uplink):
        return "paper"
    if (collective in ("packed", "ring", "rsag")
            and quant.packed_lane_bits(qcfg.bits, num_shards) > 32):
        return "int"
    return collective


def wire_phase_bits_per_param(collective: str, qcfg: QuantConfig,
                              axis_sizes: Sequence[int]) -> Dict[str, float]:
    """Per-device wire bits per parameter, split by collective PHASE.

    One-shot psum modes ship everything in a single phase ({"psum": b});
    the ring charges its hop total as {"ring_hops": b}; rsag splits into
    {"reduce_scatter": b_rs, "all_gather": b_ag} — the growing-lane scatter
    hops vs the final-lane gather redistribution — so energy/latency models
    can charge phases with different radio duty cycles separately.  Values
    sum to :func:`wire_bits_per_param`.
    """
    axis_sizes = tuple(int(s) for s in axis_sizes)
    num_shards = 1
    for s in axis_sizes:
        num_shards *= s
    eff = effective_wire_format(collective, qcfg, num_shards,
                                axis_sizes=axis_sizes)
    if eff == "paper":
        return {"psum": 32.0}
    if eff == "int":
        container = _int_container(qcfg.bits, num_shards)
        return {"psum": {jnp.int8: 8.0, jnp.int16: 16.0,
                         jnp.int32: 32.0}[container]}
    if eff == "packed":
        lane = quant.packed_lane_bits(qcfg.bits, num_shards)
        return {"psum": 32.0 / (32 // lane)}
    if eff == "ring":
        total, m = 0.0, 1
        for k in axis_sizes:
            if k <= 1:
                continue
            lane = quant.packed_lane_bits(qcfg.bits, m)
            total += (k - 1) * 32.0 / (32 // lane)
            m *= k
        return {"ring_hops": total}
    rs, ag, m = 0.0, 0.0, 1  # rsag: chunk = 1/K of the vector per hop
    for k in axis_sizes:
        if k <= 1:
            continue
        for h in range(1, k):
            lane = quant.packed_lane_bits(qcfg.bits, m * h)
            rs += 32.0 / (32 // lane) / k
        lane_k = quant.packed_lane_bits(qcfg.bits, m * k)
        ag += (k - 1) * 32.0 / (32 // lane_k) / k
        m *= k
    return {"reduce_scatter": rs, "all_gather": ag}


def wire_bits_per_param(collective: str, qcfg: QuantConfig,
                        axis_sizes: Sequence[int]) -> float:
    """Per-device wire bits per parameter actually sent by the collective
    (after fallbacks), summed over every hop/phase.

    "paper" charges the f32 psum payload (32); "int" the integer container;
    "packed" the guard-lane u32 words; "ring" (K_l−1) full-vector hops per
    level at the level's lane width; "rsag" the growing-lane chunk hops of
    both phases; "auto" whatever it resolves to.  The psum modes ship each
    param once per device (the all-reduce doubling is a topology cost,
    charged in utils/flops).
    """
    return sum(wire_phase_bits_per_param(collective, qcfg,
                                         axis_sizes).values())


# ---------------------------------------------------------------------------
# WirePlan: everything the collective needs, decided once at trace time
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WirePlan:
    """Static plan for one distributed aggregation.

    ``mode`` is what the caller asked for (possibly "auto"); ``resolved``
    the concrete mode "auto" picked (== mode otherwise); ``effective`` the
    format that actually hits the wire after the degenerate fallbacks —
    the key ``_REDUCERS`` dispatches on and the one whose bytes
    ``wire_bits`` charges.
    """
    mode: str
    resolved: str
    effective: str
    quant: QuantConfig
    axes: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    num_shards: int
    wire_bits: float


def make_wire_plan(collective: str, qcfg: QuantConfig,
                   axes: Sequence[str],
                   axis_sizes: Sequence[int]) -> WirePlan:
    """Build the aggregation plan: resolve "auto", apply fallbacks, price
    the wire.  Pure Python — safe to call at trace time (``make_fl_round``)
    or from host-side accounting (dryrun / energy / benchmarks)."""
    axes = tuple(axes)
    axis_sizes = tuple(int(s) for s in axis_sizes)
    num_shards = 1
    for s in axis_sizes:
        num_shards *= s
    resolved = (resolve_auto(qcfg, axis_sizes) if collective == "auto"
                else collective)
    if resolved not in COLLECTIVES:
        raise ValueError(f"unknown collective {resolved!r}")
    effective = effective_wire_format(resolved, qcfg, num_shards,
                                      axis_sizes=axis_sizes)
    wire_bits = wire_bits_per_param(resolved, qcfg, axis_sizes)
    return WirePlan(mode=collective, resolved=resolved, effective=effective,
                    quant=qcfg, axes=axes, axis_sizes=axis_sizes,
                    num_shards=num_shards, wire_bits=wire_bits)


# ---------------------------------------------------------------------------
# plan execution: shared front/back-end + per-mode code-sum strategies
# ---------------------------------------------------------------------------

def aggregate(plan: WirePlan, delta: PyTree, alpha: jnp.ndarray,
              lam: jnp.ndarray, key) -> PyTree:
    """Run the planned collective inside shard_map (manual over plan.axes).

    Every quantized mode quantizes the weighted delta to the exact same
    integer codes (same per-leaf PRNG streams) and computes the exact
    integer sum over the cohort, so the aggregated model is bit-identical
    across "int"/"packed"/"ring"/"rsag" — only the wire differs.
    """
    if plan.effective == "paper":
        return _exec_paper(plan, delta, alpha, lam, key)
    qcfg = plan.quant
    scale = float(plan.num_shards)
    w = (alpha * lam).astype(jnp.float32)
    with obs_trace.phase_span("wire/psum"):
        den = jax.lax.psum(w, plan.axes)

    leaves, treedef = jax.tree_util.tree_flatten(delta)
    keys = jax.random.split(key, len(leaves))
    with obs_trace.phase_span("wire/quantize_pack"):
        # the lam-weighting is the quantizer's input prep — wire front-end
        xs = [leaf.astype(jnp.float32) * (w * scale) for leaf in leaves]
    n = sum(leaf.size for leaf in leaves)
    deq = _REDUCERS[plan.effective](plan, xs, keys, n)  # flat f32 Σ codes / G
    with obs_trace.phase_span("wire/unpack_dequant"):
        # renormalize + re-leaf the dequantized sum — wire back-end
        deq = deq / (jnp.maximum(den, EPS) * scale)
        out, offset = [], 0
        for leaf in leaves:
            out.append(deq[offset: offset + leaf.size].reshape(leaf.shape))
            offset += leaf.size
    return jax.tree_util.tree_unflatten(treedef, out)


def _exec_paper(plan: WirePlan, delta, alpha, lam, key) -> PyTree:
    """Paper-faithful collective: quantize-dequantize locally (the uplink
    payload is n-bit), then float all-reduce of the weighted survivors."""
    qcfg = plan.quant
    if qcfg.enabled and qcfg.quantize_uplink:
        with obs_trace.phase_span("wire/quantize_pack"):
            delta = quant.quantize_tree(delta, key, qcfg)
    w = (alpha * lam).astype(jnp.float32)
    den = jax.lax.psum(w, plan.axes)

    def agg(dl):
        with obs_trace.phase_span("wire/psum"):
            num = jax.lax.psum(dl.astype(jnp.float32) * w, plan.axes)
        return num / jnp.maximum(den, EPS)

    return jax.tree_util.tree_map(agg, delta)


def _flat_codes(plan: WirePlan, xs: List[jax.Array],
                keys: jax.Array) -> jax.Array:
    """Quantize every (weighted, scaled) leaf with its own PRNG stream and
    flatten end to end (``quant.flat_buffer``) — the codes every quantized
    mode agrees on bit-for-bit.  ``use_pallas`` routes through the
    quantize kernel (same key -> same rounding noise -> same codes as the
    pure path)."""
    qcfg = plan.quant
    if qcfg.use_pallas:
        from repro.kernels import ops as kops
        return quant.flat_buffer([
            kops.stochastic_quantize_codes(
                x, k, qcfg.bits, clip=qcfg.clip, stochastic=qcfg.stochastic)
            for x, k in zip(xs, keys)])
    return quant.flat_buffer([
        quant.quantize_codes(x, k, qcfg.bits, clip=qcfg.clip,
                             stochastic=qcfg.stochastic)
        for x, k in zip(xs, keys)])


def _flat_noise(xs: List[jax.Array], keys: jax.Array) -> jax.Array:
    """The per-leaf rounding-noise streams, flattened end to end (what the
    fused quantize+pack kernels consume so their codes match the pure
    path)."""
    return quant.flat_buffer([
        jax.random.uniform(k, x.shape, dtype=jnp.float32)
        for x, k in zip(xs, keys)])


def _reduce_int(plan: WirePlan, xs, keys, n: int) -> jax.Array:
    """codes cross the wire in the smallest int container (one psum)."""
    qcfg = plan.quant
    with obs_trace.phase_span("wire/quantize_pack"):
        codes = _flat_codes(plan, xs, keys)
    container = _int_container(qcfg.bits, plan.num_shards)
    with obs_trace.phase_span("wire/psum"):
        total = jax.lax.psum(codes.astype(container), plan.axes)
    with obs_trace.phase_span("wire/unpack_dequant"):
        return quant.dequantize_codes(total.astype(jnp.int32), qcfg.bits,
                                      clip=qcfg.clip)


def _reduce_packed(plan: WirePlan, xs, keys, n: int) -> jax.Array:
    """guard-lane u32 psum: one bit-packed word vector crosses the wire.

    Dropped shards (λ=0) quantize a zero delta to the zero code
    deterministically (floor(0+u)=0 for u<1), so every shard contributes
    exactly one +G bias per lane — the unbias is a constant K·G.
    """
    qcfg = plan.quant
    lane = quant.packed_lane_bits(qcfg.bits, plan.num_shards)
    if qcfg.use_pallas:
        from repro.kernels import ops as kops
        with obs_trace.phase_span("wire/quantize_pack"):
            xcat = quant.flat_buffer(xs)
            words = kops.quantize_pack(xcat, None, qcfg.bits, clip=qcfg.clip,
                                       lane_bits=lane,
                                       stochastic=qcfg.stochastic,
                                       u=_flat_noise(xs, keys))
        with obs_trace.phase_span("wire/psum"):
            total = jax.lax.psum(words, plan.axes)      # u32 on the wire
        with obs_trace.phase_span("wire/unpack_dequant"):
            return kops.unpack_dequantize(total, qcfg.bits, n,
                                          clip=qcfg.clip, lane_bits=lane,
                                          sum_of=plan.num_shards)
    with obs_trace.phase_span("wire/quantize_pack"):
        codes = _flat_codes(plan, xs, keys)
        words = quant.pack_codes(codes, qcfg.bits, lane_bits=lane)
    with obs_trace.phase_span("wire/psum"):
        total = jax.lax.psum(words, plan.axes)          # u32 on the wire
    with obs_trace.phase_span("wire/unpack_dequant"):
        code_sum = quant.unpack_codes(total, qcfg.bits, n, lane_bits=lane,
                                      sum_of=plan.num_shards)
        return quant.dequantize_codes(code_sum, qcfg.bits, clip=qcfg.clip)


def _reduce_ring(plan: WirePlan, xs, keys, n: int) -> jax.Array:
    """native-width ppermute ring: the full packed vector circles the
    cohort, each hop accumulating into an int32 register tree; multi-axis
    cohorts run nested rings re-packed at the sum width between levels.

    Hop schedule (``qcfg.pipeline_hops``, the PR-8 default)::

        sequential (False)            pipelined / double-buffered (True)
        ------------------            ----------------------------------
        for h in 1..K-1:              b1 = ppermute(buf)         # prime
          b = ppermute(b)             for h in 1..K-2:   # one lax.scan
          acc += unpack(b)              b_next = ppermute(b)  # hop h+1 ...
                                        acc += unpack(b)      # ... overlaps
                                        b = b_next            #     hop h
                                      acc += unpack(b)       # trailing

    Both orders accumulate ppermute^h(buf) for h = 1..K-1 — bit-identical;
    the pipelined form issues the NEXT hop's ppermute before the current
    hop's Pallas repack so the wire transfer and the accumulate overlap on
    hardware with async collectives.  Under ``use_pallas`` the pipelined
    path also fuses the quantize->pack front-end into the
    ``quantize_pack_chunk`` megakernel, emitting the wire buffer AND the
    own-code register tree in one pass (the separate repack-init pass of
    the sequential path disappears)."""
    qcfg = plan.quant
    bits = qcfg.bits
    with obs_trace.phase_span("wire/quantize_pack"):
        if qcfg.use_pallas:
            from repro.kernels import ops as kops
            xcat = quant.flat_buffer(xs)
            if qcfg.pipeline_hops:
                # fused front-end: buf and acc in ONE megakernel pass
                words, chunks = kops.quantize_pack_chunk(
                    xcat, None, bits, clip=qcfg.clip, lane_bits=bits,
                    stochastic=qcfg.stochastic, num_chunks=1,
                    u=_flat_noise(xs, keys))
                buf, acc = words[0], chunks[0]
            else:
                buf = kops.quantize_pack(xcat, None, bits, clip=qcfg.clip,
                                         lane_bits=bits,
                                         stochastic=qcfg.stochastic,
                                         u=_flat_noise(xs, keys))
                # own codes = exact unpack of the freshly packed buffer
                acc = kops.repack(buf, jnp.zeros((n,), jnp.int32), bits, n,
                                  lane_bits=bits, sum_of=1)
        else:
            acc = _flat_codes(plan, xs, keys)
            buf = quant.pack_codes(acc, bits, lane_bits=bits)

    m = 1  # codes per register so far (partial-sum multiplicity)
    with obs_trace.phase_span("wire/ring_hops"):
        for axis, K in zip(plan.axes, plan.axis_sizes):
            if K <= 1:
                continue
            lane = quant.packed_lane_bits(bits, m)
            if m > 1:  # level transition: re-pack partial sums at sum width
                if qcfg.use_pallas:
                    from repro.kernels import ops as kops
                    buf = kops.pack_sums(acc, bits, lane_bits=lane, sum_of=m)
                else:
                    buf = quant.pack_codes(acc, bits, lane_bits=lane,
                                           sum_of=m)
            perm = [(j, (j + 1) % K) for j in range(K)]

            def accum(b, a, *, lane=lane, m=m):
                if qcfg.use_pallas:
                    from repro.kernels import ops as kops
                    return kops.repack(b, a, bits, n, lane_bits=lane,
                                       sum_of=m)
                return a + quant.unpack_codes(b, bits, n, lane_bits=lane,
                                              sum_of=m)

            if qcfg.pipeline_hops:
                b = jax.lax.ppermute(buf, axis, perm)     # prime hop 1

                def hop_pipe(carry, _, *, axis=axis, accum=accum):
                    b, a = carry
                    b_next = jax.lax.ppermute(b, axis, perm)  # hop h+1 ...
                    a = accum(b, a)                       # ... while h lands
                    return (b_next, a), None

                (b, acc), _ = jax.lax.scan(hop_pipe, (b, acc), None,
                                           length=K - 2)
                acc = accum(b, acc)                       # trailing hop K-1
            else:
                def hop(carry, _, *, axis=axis, accum=accum):
                    b, a = carry
                    b = jax.lax.ppermute(b, axis, perm)
                    a = accum(b, a)
                    return (b, a), None

                (buf, acc), _ = jax.lax.scan(hop, (buf, acc), None,
                                             length=K - 1)
            m *= K
    with obs_trace.phase_span("wire/unpack_dequant"):
        return quant.dequantize_codes(acc, bits, clip=qcfg.clip)


def _rsag_level(plan: WirePlan, codes: jax.Array, axis: str, K: int,
                unit: int, n: int, *, final: bool = False,
                front: Tuple[jax.Array, jax.Array] | None = None
                ) -> jax.Array:
    """One reduce-scatter + all-gather level over cohort axis ``axis``.

    ``codes`` holds flat partial sums of ``unit`` codes; returns flat sums
    of ``unit``·K.  The vector splits into K chunks of C = ceil(n/K) (the
    pad tail rides along as zero codes).  Scatter hop h ships ONE chunk of
    partial sums of ``unit``·h codes at lane n+⌈log2(unit·h)⌉; the gather
    phase redistributes the finished chunks at the final lane.  Every
    payload at lane L is biased by the lane-symmetric ``lane_bias(L)``
    (not the count-dependent m·G) so all hops of an equal-lane group share
    static pack/unpack constants and run as ONE ``lax.scan`` — the traced
    collective count stays O(log K) instead of O(K).

    ``front`` (level 0 under ``use_pallas`` + ``pipeline_hops``) is the
    ``quantize_pack_chunk`` megakernel's (packed words (K, Wc), chunks
    (K, C)) pair: the chunk split AND hop 1's outgoing payload come
    pre-computed in one fused pass, replacing both the per-leaf quantize
    passes and the first ``pack_sums`` (hop 1 is always its own equal-lane
    group at unit=1 — lane(h=2) = lane(h=1)+1).  ``codes`` is ignored then.

    Hop schedules (``qcfg.pipeline_hops``): the reduce-scatter is
    inherently SEQUENTIAL — hop h+1's payload is the pack of hop h's
    accumulate, a true data dependency — so only its front-end fuses.  The
    all-gather forwards a finished buffer unchanged, so it double-buffers
    exactly like the ring: the ppermute of hop t+1 is issued before the
    chunk store of hop t (prime / scan over t=1..K-2 / trailing store),
    same stores in the same order — bit-identical to the sequential scan.

    ``final`` marks the LAST level: its all-gather chunks are the finished
    code sums, so the store dequantizes straight out of the wire words
    into the f32 output (the fused ``unpack_dequantize`` scatter variant
    when ``use_pallas``) and the int32 round-trip of the plain
    ``unpack_codes`` store disappears — the return is flat f32, already
    dequantized.
    """
    qcfg = plan.quant
    bits = qcfg.bits
    C = -(-n // K)
    if front is not None:
        front_words, chunks = front
    else:
        chunks = quant.flat_buffer([codes], K * C).reshape(K, C)
    idx = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % K) for j in range(K)]

    def pack_fn(c, lane):
        b = quant.lane_bias(lane)
        if qcfg.use_pallas:
            from repro.kernels import ops as kops
            return kops.pack_sums(c, bits, lane_bits=lane, bias=b)
        return quant.pack_codes(c, bits, lane_bits=lane, bias=b)

    def unpack_add_fn(words, chunk, lane):
        b = quant.lane_bias(lane)
        if qcfg.use_pallas:
            from repro.kernels import ops as kops
            return kops.repack(words, chunk, bits, C, lane_bits=lane, bias=b)
        return chunk + quant.unpack_codes(words, bits, C, lane_bits=lane,
                                          bias=b)

    def chunk_at(i):
        return jax.lax.dynamic_slice(chunks, (i, 0), (1, C))[0]

    def hop(carry, h, lane):
        # carry: partial sums of unit·h codes for chunk (idx-(h-1)) mod K;
        # after the permute+accumulate: unit·(h+1) for chunk (idx-h) mod K
        recv = jax.lax.ppermute(pack_fn(carry, lane), axis, perm)
        return unpack_add_fn(recv, chunk_at((idx - h) % K), lane)

    # ---- reduce-scatter: hops grouped by (equal) lane width --------------
    # (sequential by construction: hop h+1 ships the PACK of hop h's
    # accumulate — only the front-end fuses, via ``front``)
    with obs_trace.phase_span("wire/reduce_scatter"):
        groups: List[Tuple[int, List[int]]] = []
        for h in range(1, K):
            lane = quant.packed_lane_bits(bits, unit * h)
            if groups and groups[-1][0] == lane:
                groups[-1][1].append(h)
            else:
                groups.append((lane, [h]))
        carry = chunk_at(idx)
        if front is not None:
            # hop 1's payload is the megakernel's pre-packed own chunk
            lane1 = groups[0][0]
            payload = jax.lax.dynamic_slice(
                front_words, (idx, 0), (1, front_words.shape[1]))[0]
            recv = jax.lax.ppermute(payload, axis, perm)
            carry = unpack_add_fn(recv, chunk_at((idx - 1) % K), lane1)
            groups = groups[1:]
        for lane, hs in groups:
            if len(hs) == 1:
                carry = hop(carry, hs[0], lane)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, h, lane=lane: (hop(c, h, lane), None),
                    carry, jnp.arange(hs[0], hs[-1] + 1))
    # carry now holds the FULL sum (unit·K codes) of chunk (idx+1) mod K

    # ---- all-gather: redistribute finished chunks at the final lane ------
    with obs_trace.phase_span("wire/all_gather"):
        return _rsag_all_gather(plan, carry, axis, K, unit, n, C, idx,
                                perm, pack_fn, final=final)


def _rsag_all_gather(plan: WirePlan, carry: jax.Array, axis: str, K: int,
                     unit: int, n: int, C: int, idx, perm, pack_fn, *,
                     final: bool) -> jax.Array:
    """The all-gather phase of one rsag level (span-scoped; see
    :func:`_rsag_level` for the schedule semantics)."""
    qcfg = plan.quant
    bits = qcfg.bits
    lane_k = quant.packed_lane_bits(bits, unit * K)
    bias_k = quant.lane_bias(lane_k)
    buf = pack_fn(carry, lane_k)

    if final:
        # fused store: finished chunks dequantize straight from the wire
        def unpack_store(words):
            if qcfg.use_pallas:
                from repro.kernels import ops as kops
                return kops.unpack_dequantize(words, bits, C,
                                              clip=qcfg.clip,
                                              lane_bits=lane_k, bias=bias_k)
            return quant.dequantize_codes(
                quant.unpack_codes(words, bits, C, lane_bits=lane_k,
                                   bias=bias_k), bits, clip=qcfg.clip)

        out = jnp.zeros((K, C), jnp.float32)
        own = quant.dequantize_codes(carry, bits, clip=qcfg.clip)
        out = jax.lax.dynamic_update_slice(out, own[None],
                                           ((idx + 1) % K, 0))

        if qcfg.pipeline_hops:
            b = jax.lax.ppermute(buf, axis, perm)       # prime hop 1

            def gather_f32_pipe(state, t):
                b, o = state
                b_next = jax.lax.ppermute(b, axis, perm)  # issue hop t+1
                o = jax.lax.dynamic_update_slice(o, unpack_store(b)[None],
                                                 ((idx + 1 - t) % K, 0))
                return (b_next, o), None

            (b, out), _ = jax.lax.scan(gather_f32_pipe, (b, out),
                                       jnp.arange(1, K - 1))
            out = jax.lax.dynamic_update_slice(            # trailing store
                out, unpack_store(b)[None], ((idx + 2 - K) % K, 0))
            return out.reshape(-1)[:n]

        def gather_f32(state, t):
            b, o = state
            b = jax.lax.ppermute(b, axis, perm)
            o = jax.lax.dynamic_update_slice(o, unpack_store(b)[None],
                                             ((idx + 1 - t) % K, 0))
            return (b, o), None

        (_, out), _ = jax.lax.scan(gather_f32, (buf, out), jnp.arange(1, K))
        return out.reshape(-1)[:n]

    out = jnp.zeros((K, C), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, carry[None], ((idx + 1) % K, 0))

    def unpack_chunk(b):
        return quant.unpack_codes(b, bits, C, lane_bits=lane_k, bias=bias_k)

    if qcfg.pipeline_hops:
        b = jax.lax.ppermute(buf, axis, perm)           # prime hop 1

        def gather_pipe(state, t):
            b, o = state
            b_next = jax.lax.ppermute(b, axis, perm)      # issue hop t+1
            o = jax.lax.dynamic_update_slice(o, unpack_chunk(b)[None],
                                             ((idx + 1 - t) % K, 0))
            return (b_next, o), None

        (b, out), _ = jax.lax.scan(gather_pipe, (b, out),
                                   jnp.arange(1, K - 1))
        out = jax.lax.dynamic_update_slice(                # trailing store
            out, unpack_chunk(b)[None], ((idx + 2 - K) % K, 0))
        return out.reshape(-1)[:n]

    def gather(state, t):
        b, o = state
        b = jax.lax.ppermute(b, axis, perm)
        o = jax.lax.dynamic_update_slice(o, unpack_chunk(b)[None],
                                         ((idx + 1 - t) % K, 0))
        return (b, o), None

    (_, out), _ = jax.lax.scan(gather, (buf, out), jnp.arange(1, K))
    return out.reshape(-1)[:n]


def _reduce_rsag(plan: WirePlan, xs, keys, n: int) -> jax.Array:
    """reduce-scatter + all-gather with growing lane widths (see
    :func:`_rsag_level`); multi-axis cohorts run one level per axis, the
    partial-sum multiplicity compounding like the ring's nested levels.
    The LAST level's all-gather stores dequantized f32 directly (fused
    ``unpack_dequantize`` under ``use_pallas``) — earlier levels must stay
    int32 codes because later levels keep summing them.

    Under ``use_pallas`` + ``pipeline_hops`` level 0's quantize->pack->
    chunk front-end fuses into ONE ``quantize_pack_chunk`` megakernel
    pass (replacing the per-leaf quantize kernels, the XLA pad/reshape
    chunking AND hop 1's ``pack_sums``); later levels chunk the previous
    level's output as before."""
    qcfg = plan.quant
    active = [(axis, int(K)) for axis, K in zip(plan.axes, plan.axis_sizes)
              if K > 1]
    front = None
    with obs_trace.phase_span("wire/quantize_pack"):
        if qcfg.use_pallas and qcfg.pipeline_hops and active:
            from repro.kernels import ops as kops
            lane0 = quant.packed_lane_bits(qcfg.bits, 1)
            front = kops.quantize_pack_chunk(
                quant.flat_buffer(xs), None,
                qcfg.bits, clip=qcfg.clip, lane_bits=lane0,
                stochastic=qcfg.stochastic, num_chunks=active[0][1],
                bias=quant.lane_bias(lane0), u=_flat_noise(xs, keys))
            codes = None
        else:
            codes = _flat_codes(plan, xs, keys)
    if not active:
        with obs_trace.phase_span("wire/unpack_dequant"):
            return quant.dequantize_codes(codes, plan.quant.bits,
                                          clip=plan.quant.clip)
    unit = 1
    for i, (axis, K) in enumerate(active):
        codes = _rsag_level(plan, codes, axis, K, unit, n,
                            final=(i == len(active) - 1),
                            front=front if i == 0 else None)
        unit *= K
    return codes  # already dequantized f32 by the final level's store


_REDUCERS = {"int": _reduce_int, "packed": _reduce_packed,
             "ring": _reduce_ring, "rsag": _reduce_rsag}
