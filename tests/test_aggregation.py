"""Error-aware aggregation (paper eq. 5/6): pure + kernel forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import QuantConfig
from repro.core import aggregation as agg
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _deltas(key, K, shape):
    return jax.random.normal(key, (K,) + shape) * 0.01


def test_error_aware_matches_manual():
    K = 5
    key = jax.random.PRNGKey(0)
    w = {"p": jnp.zeros((13,))}
    deltas = {"p": _deltas(key, K, (13,))}
    alphas = jnp.asarray([0.1, 0.2, 0.3, 0.25, 0.15])
    lam = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    out = agg.error_aware_aggregate(w, deltas, alphas, lam)
    wts = alphas * lam
    want = (deltas["p"] * wts[:, None]).sum(0) / wts.sum()
    np.testing.assert_allclose(np.asarray(out["p"]), np.asarray(want), rtol=1e-6)


def test_error_aware_ignores_failed_clients():
    """A failed client's delta must not influence the result at all."""
    K = 4
    key = jax.random.PRNGKey(1)
    w = {"p": jnp.zeros((8,))}
    deltas = {"p": _deltas(key, K, (8,))}
    poisoned = {"p": deltas["p"].at[2].set(1e9)}
    alphas = jnp.full((K,), 0.25)
    lam = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    a = agg.error_aware_aggregate(w, deltas, alphas, lam)
    b = agg.error_aware_aggregate(w, poisoned, alphas, lam)
    np.testing.assert_allclose(np.asarray(a["p"]), np.asarray(b["p"]))


def test_naive_vs_error_aware_scaling():
    """eq. 5 divides by K (shrinks with drops); eq. 6 renormalizes."""
    K = 4
    deltas = {"p": jnp.ones((K, 3))}
    w = {"p": jnp.zeros((3,))}
    alphas = jnp.full((K,), 1.0 / K)
    lam = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    naive = agg.naive_aggregate(w, deltas, lam)
    aware = agg.error_aware_aggregate(w, deltas, alphas, lam)
    np.testing.assert_allclose(np.asarray(naive["p"]), 0.5)  # 2/4
    np.testing.assert_allclose(np.asarray(aware["p"]), 1.0)  # 2/2


def test_all_failed_round_is_noop_for_error_aware():
    K = 3
    deltas = {"p": jnp.ones((K, 5))}
    w = {"p": jnp.full((5,), 7.0)}
    out = agg.error_aware_aggregate(w, deltas, jnp.full((K,), 1 / 3),
                                    jnp.zeros((K,)))
    np.testing.assert_allclose(np.asarray(out["p"]), 7.0)


def test_int_container_selection():
    assert agg._int_container(8, 16) == jnp.int16   # 7+4+1 = 12 bits
    assert agg._int_container(8, 512) == jnp.int32  # 7+9+1 = 17 > 15 bits
    assert agg._int_container(16, 4) == jnp.int32


def test_effective_wire_format_fallbacks():
    """Degenerate configs must surface the format actually sent: unquantized
    uplinks are f32 psums; lane>32 packings are int psums."""
    q8 = QuantConfig(bits=8)
    for mode in ("paper", "int", "packed", "ring", "rsag"):
        assert agg.effective_wire_format(mode, q8, 8) == \
            ("paper" if mode == "paper" else mode)
    q_off = QuantConfig(bits=0)
    q_nouplink = QuantConfig(bits=8, quantize_uplink=False)
    for q in (q_off, q_nouplink):
        for mode in ("int", "packed", "ring", "rsag", "auto"):
            assert agg.effective_wire_format(mode, q, 8) == "paper"
    q30 = QuantConfig(bits=30)
    assert agg.effective_wire_format("packed", q30, 8) == "int"  # lane 33
    assert agg.effective_wire_format("ring", q30, 8) == "int"
    assert agg.effective_wire_format("rsag", q30, 8) == "int"
    assert agg.effective_wire_format("auto", q30, 8) == "int"
    assert agg.effective_wire_format("int", q30, 8) == "int"
    assert agg.effective_wire_format("packed", q30, 2) == "packed"  # lane 31
    with pytest.raises(ValueError):
        agg.effective_wire_format("bogus", q8, 8)


def test_wire_bits_per_param_matches_wire():
    """The telemetry number equals the bits each device really ships."""
    q8 = QuantConfig(bits=8)
    assert agg.wire_bits_per_param("paper", q8, (2,)) == 32.0
    assert agg.wire_bits_per_param("int", q8, (2,)) == 16.0    # int16 psum
    assert agg.wire_bits_per_param("packed", q8, (2,)) == 32.0 / 3  # lane 9
    assert agg.wire_bits_per_param("ring", q8, (2,)) == 8.0    # 1 native hop
    # ring hops accumulate: K=16 -> 15 hops x 8 bits
    assert agg.wire_bits_per_param("ring", q8, (16,)) == 15 * 8.0
    # two-level cohort: native hop + sum-width hops (lane 9 -> 3 codes/word)
    got = agg.wire_bits_per_param("ring", q8, (2, 4))
    assert got == 1 * 8.0 + 3 * (32.0 / 3)
    # lane>32 fallback charges the int container, not the requested format
    q30 = QuantConfig(bits=30)
    assert agg.wire_bits_per_param("packed", q30, (8,)) == 32.0
    assert agg.wire_bits_per_param("ring", q30, (8,)) == 32.0
    assert agg.wire_bits_per_param("rsag", q30, (8,)) == 32.0
    # unquantized uplink -> the f32 psum
    assert agg.wire_bits_per_param("ring", QuantConfig(bits=0), (4,)) == 32.0


def test_rsag_wire_bits_growing_lanes():
    """rsag charges one 1/K chunk per hop: scatter hops at the growing
    n+ceil(log2 h) lane, gather hops at the final lane — capped near
    2·(n+⌈log2 K⌉) regardless of K (the ring's cost grows with K-1)."""
    q8 = QuantConfig(bits=8)
    # K=2: one scatter hop at lane 8 (cpw 4) + one gather hop at lane 9
    # (cpw 3), each carrying half the vector
    want_k2 = 0.5 * (32.0 / 4) + 0.5 * (32.0 / 3)
    assert abs(agg.wire_bits_per_param("rsag", q8, (2,)) - want_k2) < 1e-9
    # K=16: 28.5 bits/param — between packed (16) and ring (120)
    got = agg.wire_bits_per_param("rsag", q8, (16,))
    assert abs(got - 28.5) < 1e-9
    assert (agg.wire_bits_per_param("packed", q8, (16,)) < got
            < agg.wire_bits_per_param("ring", q8, (16,)))
    # the cap: doubling K barely moves the cost (vs the ring's ~2x)
    k32 = agg.wire_bits_per_param("rsag", q8, (32,))
    assert k32 < got * 1.2
    assert agg.wire_bits_per_param("ring", q8, (32,)) > 2 * 100
    # phases sum to the total and split scatter/gather
    phases = agg.wire_phase_bits_per_param("rsag", q8, (16,))
    assert set(phases) == {"reduce_scatter", "all_gather"}
    assert abs(sum(phases.values()) - got) < 1e-9
    assert phases["all_gather"] == 15 * (32.0 / 2) / 16  # 15 hops at lane 12
    # one-shot modes report a single psum phase
    assert agg.wire_phase_bits_per_param("packed", q8, (2,)) == \
        {"psum": 32.0 / 3}
    assert set(agg.wire_phase_bits_per_param("ring", q8, (2,))) == \
        {"ring_hops"}


def test_resolve_auto_picks_byte_minimal_mode():
    """"auto" = argmin wire_bits_per_param over the quantized modes: ring
    for small cohorts, packed once the per-hop ring cost blows up, int
    after the lane>32 fallback, paper when the uplink is unquantized."""
    q8 = QuantConfig(bits=8)
    assert agg.resolve_auto(q8, (2,)) == "ring"
    # two-level (2,4) cohort: the level-1 ring hops at the widened lane
    # already cost 40 bits/param — the one-shot packed psum (16) wins
    assert agg.resolve_auto(q8, (2, 4)) == "packed"
    assert agg.resolve_auto(q8, (16,)) == "packed"
    assert agg.resolve_auto(QuantConfig(bits=30), (8,)) == "int"
    assert agg.resolve_auto(QuantConfig(bits=0), (16,)) == "paper"
    assert agg.resolve_auto(QuantConfig(bits=8, quantize_uplink=False),
                            (2,)) == "paper"
    # the resolution is never worse than any concrete quantized mode
    for bits in (1, 2, 4, 8, 16):
        for sizes in ((2,), (3,), (16,), (2, 4), (4, 16)):
            q = QuantConfig(bits=bits)
            best = agg.resolve_auto(q, sizes)
            got = agg.wire_bits_per_param(best, q, sizes)
            for mode in agg.AUTO_ORDER:
                assert got <= agg.wire_bits_per_param(mode, q, sizes) + 1e-9


def test_make_wire_plan_resolves_and_prices():
    """The plan carries the resolved mode, the post-fallback effective
    format, and the wire bits telemetry/energy must charge."""
    q8 = QuantConfig(bits=8)
    plan = agg.make_wire_plan("auto", q8, ("data",), (2,))
    assert (plan.mode, plan.resolved, plan.effective) == \
        ("auto", "ring", "ring")
    assert plan.wire_bits == 8.0
    assert plan.num_shards == 2
    plan16 = agg.make_wire_plan("auto", q8, ("data",), (16,))
    assert (plan16.resolved, plan16.effective) == ("packed", "packed")
    q30 = QuantConfig(bits=30)
    fb = agg.make_wire_plan("rsag", q30, ("data",), (8,))
    assert (fb.resolved, fb.effective, fb.wire_bits) == ("rsag", "int", 32.0)
    off = agg.make_wire_plan("packed", QuantConfig(bits=0), ("data",), (4,))
    assert (off.effective, off.wire_bits) == ("paper", 32.0)
    with pytest.raises(ValueError):
        agg.make_wire_plan("bogus", q8, ("data",), (2,))


def test_aggregate_kernel_matches_pure():
    """Pallas masked_aggregate == eq. 6 numerator/denominator."""
    K, D = 10, 4096
    upd = jax.random.normal(jax.random.PRNGKey(2), (K, D))
    alphas = jax.random.uniform(jax.random.PRNGKey(3), (K,))
    lam = (jax.random.uniform(jax.random.PRNGKey(4), (K,)) > 0.3).astype(jnp.float32)
    got = kops.masked_aggregate(upd, alphas * lam)
    want = kref.masked_aggregate_ref(upd, alphas * lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# the wire's flat buffers (quantization.flat_buffer)
# ---------------------------------------------------------------------------

def _tree(kind):
    """Leaf lists for the flat-buffer tests: empty, odd-sized, many-leaf."""
    key = jax.random.PRNGKey(7)
    if kind == "empty":
        return []
    if kind == "zero_size_leaf":
        return [jnp.zeros((0, 3)), jnp.arange(5.0), jnp.zeros((0,))]
    if kind == "odd":
        return [jax.random.normal(key, (3, 7)), jnp.arange(11.0),
                jax.random.normal(key, (1, 1, 5))]
    return [jax.random.normal(jax.random.fold_in(key, i), (i % 4 + 1, 3))
            for i in range(40)]


@pytest.mark.parametrize("kind", ["empty", "zero_size_leaf", "odd", "many"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.uint32])
@pytest.mark.parametrize("pad", [0, 1, 13])
def test_flat_buffer_equals_concatenate_and_pad(kind, dtype, pad):
    """The buffer writes are exact: bit for bit the concatenation of the
    flattened pieces, zero-padded to ``size``."""
    from repro.core import quantization as quant
    pieces = [(p * 1000).astype(dtype) for p in _tree(kind)]
    total = sum(p.size for p in pieces)
    if not pieces:  # nothing to concatenate: a zero vector of the size
        got = quant.flat_buffer(pieces, pad)
        assert got.shape == (pad,) and not np.asarray(got).any()
        return
    want = jnp.pad(jnp.concatenate([p.reshape(-1) for p in pieces]),
                   (0, pad))
    got = jax.jit(lambda ps: quant.flat_buffer(ps, total + pad))(pieces)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(  # default size: the concatenation
        np.asarray(quant.flat_buffer(pieces)),
        np.asarray(jnp.concatenate([p.reshape(-1) for p in pieces])))
    with pytest.raises(ValueError):
        quant.flat_buffer(pieces + [jnp.ones((2,), dtype)], total)


@pytest.mark.parametrize("n", [1, 97, 4096, 4099])
@pytest.mark.parametrize("bits,lane,sum_of", [(8, 8, 1), (8, 10, 4),
                                              (2, 3, 2), (5, 5, 1)])
def test_pack_codes_unchanged_by_the_flat_buffer(n, bits, lane, sum_of):
    """``pack_codes`` writes its word planes through ``flat_buffer``; the
    words equal the jnp.pad + reshape layout it replaced."""
    from repro.core import quantization as quant
    g = 2 ** (bits - 1)
    codes = jax.random.randint(jax.random.PRNGKey(n), (n,), -g * sum_of,
                               (g - 1) * sum_of + 1, jnp.int32)
    cpw = quant.codes_per_word(bits, lane_bits=lane)
    W = quant.packed_words(n, bits, lane_bits=lane)
    biased = codes.astype(jnp.uint32) + jnp.uint32(g * sum_of)
    planes = jnp.pad(biased, (0, cpw * W - n)).reshape(cpw, W)
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * lane)[:, None]
    want = jnp.sum(planes << shifts, axis=0, dtype=jnp.uint32)
    got = quant.pack_codes(codes, bits, lane_bits=lane, sum_of=sum_of)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = quant.unpack_codes(got, bits, n, lane_bits=lane, sum_of=sum_of)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))
