"""The trace reduction: its interval arithmetic, the span join, declared
spans at any depth, host spans, and one small trace recorded on a TPU
v5e."""
import pytest

from benchkit import trace_reduce as tr


def test_op_name_of_a_tpu_event():
    assert tr._op_name("%fusion.2 = u32[3]{0} fusion(), kind=kLoop") == \
        "fusion.2"
    assert tr._op_name("dot_general.1") == "dot_general.1"


def test_outermost_span_wins():
    path = "jit(f)/shard_map/wire/quantize_pack/pallas/fl/apply/mul"
    assert tr.outermost_span(path) == "wire/quantize_pack"
    assert tr.outermost_span("fl/local_steps/dot") == "fl/local_steps"
    assert tr.outermost_span("jit(f)/other/add") is None
    assert tr.outermost_span(None) is None


def test_parse_hlo_op_names():
    text = ('%fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(step)/fl/apply/add" source_file="x"}\n'
            '%copy.1 = f32[4]{0} copy(%p)\n')
    assert tr.parse_hlo_op_names(text) == {
        "fusion.3": "jit(step)/fl/apply/add"}


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tr.union_length(iv) == 30
    assert tr.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_leaf_ops_drops_enclosing_ops():
    outer = tr.Op("while.1", "m", 0, 100)
    a = tr.Op("fusion.1", "m", 10, 20)
    b = tr.Op("fusion.2", "m", 30, 60)
    c = tr.Op("fusion.3", "m", 120, 130)
    assert [o.name for o in tr.leaf_ops([outer, a, b, c])] == [
        "fusion.1", "fusion.2", "fusion.3"]


def test_name_gap_takes_the_innermost_covering_span():
    spans = [(0, 200, "bench/window"), (10, 60, "bench/run_rounds"),
             (20, 40, "bench/input_prep"), (120, 160, "bench/run_rounds"),
             (123, 159, "sim/history"), (124, 126, "sim/sync")]
    assert tr.name_gap((22, 38), spans) == "bench/input_prep"
    assert tr.name_gap((45, 58), spans) == "bench/run_rounds"
    assert tr.name_gap((70, 90), spans) == "host: outside any span"
    # a declared span under most of the gap beats the outer one that
    # covers all of it; one under less than half of it does not
    assert tr.name_gap((124, 160), spans) == "sim/history"
    assert tr.name_gap((118, 124), spans) == "bench/run_rounds"


def test_host_span_totals_count_inside_the_window():
    spans = [(0, 100, "bench/window"), (5, 15, "sim/sync"),
             (20, 22, "sim/sync"), (95, 110, "sim/sync"),
             (120, 130, "sim/sync"), (30, 40, "sim/stack")]
    secs, count = tr.host_span_totals(spans, ("sim/sync",), 0, 100)
    assert count == {"sim/sync": 3}
    assert secs["sim/sync"] == pytest.approx(17e-9)
    assert tr.host_span_totals(spans, (), 0, 100) == ({}, {})


def test_declared_span_at_any_depth(tmp_path):
    """A jitted function whose known outer span holds a declared inner
    one, traced on the CPU: the inner span reads its own ops' time, the
    outermost join and its coverage are what they are without it, and a
    declared host span is counted inside the window only."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("fl/local_steps"):
            y = jnp.tanh(x @ x)
            with jax.named_scope("test/inner"):
                z = jnp.sin(y @ y)
            return y * z

    fn = jax.jit(f)
    x = jnp.full((256, 256), 1e-3, jnp.float32)
    fn(x).block_until_ready()
    text = fn.lower(x).compile().as_text()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("test/host"):
                x = fn(x)
        x.block_until_ready()
    with jax.profiler.TraceAnnotation("test/host"):
        fn(x).block_until_ready()
    jax.profiler.stop_trace()

    class Dev:
        device_kind = "cpu"
    rec = {"rounds": 5, "hlo": {text.split(None, 2)[1].rstrip(","): text}}
    plain = tr.reduce(str(tmp_path), rec, [Dev()])
    nested = tr.reduce(str(tmp_path), rec, [Dev()],
                       spans=("test/inner", "fl/local_steps"),
                       host=("test/host",))
    for key in ("span_s", "coverage", "provenanced", "busy_s", "window_s",
                "op_s"):
        assert nested[key] == plain[key], key
    assert set(plain["span_s"]) == {"fl/local_steps"}
    assert plain["span_in_s"] == {} and plain["host_span_n"] == {}
    inner = nested["span_in_s"]["test/inner"]
    outer = nested["span_in_s"]["fl/local_steps"]
    assert 0 < inner < outer == nested["span_s"]["fl/local_steps"]
    assert nested["host_span_n"] == {"test/host": 5}
    assert 0 < nested["host_span_s"]["test/host"] <= nested["window_s"]
    assert nested["counters"] == {}


def test_recorded_chip_trace(chip_ctx):
    """A tiny FL round traced on one TPU v5e: nearly all device op time
    carries an op_name, the join attributes most of it to the round's
    spans (coverage is over all op time), and busy <= window."""
    meta, ctx = chip_ctx
    assert 0 < ctx["busy_s"] <= ctx["window_s"]
    assert ctx["coverage"] >= meta["min_coverage"]
    assert ctx["provenanced"] >= meta["min_provenanced"]
    assert ctx["coverage"] <= ctx["provenanced"] <= 1.0
    assert "fl/local_steps" in ctx["span_s"]
    assert any(s.startswith("wire/") for s in ctx["span_s"])
    assert len(ctx["breakdown"]["device_ops"]) == 10
    assert sum(ctx["span_s"].values()) <= ctx["op_s"] + 1e-12
