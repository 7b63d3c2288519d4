"""The yardstick's counts against the repository's own counts."""
import math

import jax
import numpy as np
import pytest

from benchkit import common, fl_round, work


def test_qnn_counts_match_the_paper_and_the_model():
    from repro.models import cnn
    assert work.qnn_weights() == 421_642 == cnn.count_weights()
    assert work.qnn_macs_per_sample() == 4_241_152 == cnn.count_macs()


def test_qnn_round_flops():
    # K=10 clients x I=3 steps x batch 32, forward + backward, 2 FLOP/MAC
    assert work.qnn_round_flops(10, 3, 32) == 6 * 4_241_152 * 960
    assert abs(work.qnn_round_flops(10, 3, 32) - 24.4e9) < 0.05e9


def test_olmo_1b_at_full_depth_has_1177m_params():
    from repro.configs import get_config
    m = get_config("olmo-1b").model
    dims = {"d_model": m.d_model, "n_heads": m.n_heads,
            "n_kv_heads": m.n_kv_heads, "d_ff": m.d_ff,
            "vocab_size": m.vocab_size, "n_layers": m.n_layers}
    assert m.n_layers == 16
    assert work.olmo_param_count(dims) == 1_176_764_416 == m.param_count()


@pytest.mark.parametrize("config,layers,count", [
    ("olmo-1b-d14", 14, 1_042_546_688),
    ("olmo-1b-d12", 12, 908_328_960),
])
def test_olmo_param_count(config, layers, count):
    from repro.models import build_model
    cell = common.Cell("x", config, "long_local", 1,
                       common.load_json(f"{common.BENCH_DIR}/configs/"
                                        f"{config}.json"),
                       common.load_json(f"{common.BENCH_DIR}/traffic/"
                                        "long_local.json"), {})
    cfg = fl_round.build_config(cell)
    dims = fl_round.arch(cell).dims(cfg)
    assert dims["n_layers"] == layers
    assert work.olmo_param_count(dims) == count
    assert fl_round.arch(cell).param_count(dims) == count
    assert cfg.model.param_count() == count
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(shapes)) == count


def test_olmo_round_flops_long_local():
    dims = {"d_model": 2048, "n_heads": 16, "n_kv_heads": 16, "d_ff": 8192,
            "vocab_size": 50304, "n_layers": 16}
    f = work.olmo_round_flops(dims, 3 * 2048, 2048)
    assert f == 6 * 1_176_764_416 * 6144 + 12 * 16 * 2048 * 2048 * 6144
    assert abs(f - 4.83e13) < 0.01e13


@pytest.mark.parametrize("mode", ["packed", "int", "ring", "rsag"])
@pytest.mark.parametrize("bits,cohorts", [(8, 2), (8, 4), (4, 4), (8, 16),
                                          (2, 8)])
def test_wire_bits_match_the_program(mode, bits, cohorts):
    from repro.config.base import QuantConfig
    from repro.core import aggregation
    want = aggregation.wire_bits_per_param(mode, QuantConfig(bits=bits),
                                           (cohorts,))
    assert math.isclose(work.wire_bits_per_param(mode, bits, cohorts), want)


def test_wire_bits_auto_at_four_cohorts_is_packed():
    assert fl_round.resolved_mode("auto", 8, 4) == "packed"
    assert math.isclose(work.wire_bits_per_param("packed", 8, 4), 32 / 3)
    assert round(work.wire_bits_per_param("packed", 8, 4), 2) == 10.67


def test_wire_interface_bytes():
    d = 1_000_000
    one = work.wire_interface_bytes(d, 2, 8.0, 1)
    assert one == {"hbm": 3 * 2 * d + 2 * d, "ici": 0.0}
    four = work.wire_interface_bytes(d, 2, 32 / 3, 4)
    payload = d * 32 / 3 / 8
    assert math.isclose(four["hbm"], 6 * d + 2 * payload)
    assert math.isclose(four["ici"], 1.5 * payload)


def test_fleet_state_bytes_counts_every_vector():
    from repro.population.fleet import FleetState
    vectors = [f for f in FleetState._fields if f != "rr_cursor"]
    assert len(vectors) == work.FLEET_VECTORS
    assert work.fleet_state_bytes(10 ** 6) == 2 * (8 * 4 * 10 ** 6 + 4)


def test_least_seconds_takes_the_largest_bound():
    from benchkit import peaks
    pk = peaks.peaks_for("TPU v5 lite")
    assert work.least_seconds(819e9, 0, 0, pk) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 200e9, 0, pk) == pytest.approx(1.0)
    assert work.least_seconds(0, 0, 197e12, pk) == pytest.approx(1.0)


def test_unknown_device_has_no_peaks():
    from benchkit import peaks
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
