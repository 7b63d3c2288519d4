"""The per-layer metrics read from the TPU trace committed under
``data/tpu_v5e_fl_round``, pinned to the values the reduction gave when
the program's simulator spans (``sim/*``) were added.  A later change to
the join may add spans and metrics; it must not move these."""
import pytest

from benchkit import readers

PINNED = {
    "coverage": 0.884630944261032,
    "provenanced": 0.9583842108813103,
    "busy_s": 0.006478791,
    "window_s": 0.051010659,
    "local_steps_ms": 0.10673204545454122,
    "wire_ms": 0.023525659090909062,
    "wire_roofline": 20.760331794029067,
    "step_mfu": 0.4378501634474211,
    "idle_share": 87.2991427144668,
}


@pytest.fixture(scope="module")
def ctx(chip_ctx):
    return chip_ctx[1]


@pytest.mark.parametrize("name", ["coverage", "provenanced", "busy_s",
                                  "window_s"])
def test_trace_totals_pinned(ctx, name):
    assert ctx[name] == pytest.approx(PINNED[name], rel=1e-12, abs=0)


@pytest.mark.parametrize("name", ["local_steps_ms", "wire_ms",
                                  "wire_roofline", "step_mfu", "idle_share"])
def test_reader_pinned(ctx, name):
    assert getattr(readers, name)(ctx) == pytest.approx(PINNED[name],
                                                        rel=1e-12, abs=0)


def test_declared_outer_span_reads_its_layer(ctx):
    """Nothing encloses fl/local_steps, so declared at any depth it reads
    what the outermost join gives it."""
    assert ctx["span_in_s"]["fl/local_steps"] == \
        ctx["span_s"]["fl/local_steps"]
    assert readers.span_in_ms(ctx, ("fl/local_steps",)) == \
        readers.local_steps_ms(ctx)


def test_fleet_readers_read_nothing_in_a_round_trace(ctx):
    assert readers.fleet_ms(ctx) is None
    assert readers.fleet_roofline(ctx) is None
    assert readers.span_in_ms(ctx, ("sim/client_update",)) is None
    assert readers.host_span_count(ctx, ("sim/sync",)) is None
