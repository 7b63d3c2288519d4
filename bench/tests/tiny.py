"""Tiny cells of the benchmark's two drivers, for CPU tests.  The learning
rate is raised so that a round moves many quantization codes and the
comparison sees more than a handful of them."""
from __future__ import annotations

from benchkit import common

TINY_MODEL = {"model.n_layers": 2, "model.d_model": 128,
              "model.n_heads": 4, "model.n_kv_heads": 4,
              "model.d_ff": 256, "model.vocab_size": 512,
              "model.dtype": "float32", "fl.learning_rate": 0.5}

#: limits of the tiny FL cell, between the program's and the control's
#: readings at this size (see test_control)
TINY_FL_LIMITS = {"loss_gap": 5e-3, "survivors_gap": 0.0, "change_gap": 2e-3}
TINY_FLEET_LIMITS = {"loss_gap": 1e-4, "survivors_gap": 0.0,
                     "selected_mismatch": 0.0, "fleet_gap": 1e-5,
                     "change_gap": 1e-3}


def fl_cell(cohorts: int = 1) -> common.Cell:
    return common.Cell(
        name="tiny.fl", config_name="tiny", traffic_name="tiny",
        chips=cohorts,
        config={"arch": "olmo-1b", "reference": "ref_olmo",
                "model": {"n_layers": 2, "d_model": 128},
                "overrides": dict(TINY_MODEL)},
        traffic={"driver": "fl_round",
                 "collective": "packed" if cohorts == 1 else "auto",
                 "bits": 8, "error_prob": 0.01, "local_iters": 3,
                 "global_batch": 3 * cohorts, "seq_len": 32},
        limits=dict(TINY_FL_LIMITS))


def fleet_cell(devices: int = 4096) -> common.Cell:
    return common.Cell(
        name="tiny.fleet", config_name="mnist_cnn", traffic_name="tiny",
        chips=1,
        config=common.load_json(common.os.path.join(
            common.BENCH_DIR, "configs", "mnist_cnn.json")),
        traffic={"driver": "fleet_scan", "chunk_rounds": 2,
                 "num_samples": 1000, "num_clients": 20,
                 "overrides": {"fleet.size": devices,
                               "power.policy": "fbl_target",
                               "fleet.selection": "uniform",
                               "fl.devices_per_round": 4,
                               "fl.local_iters": 2,
                               "train.global_batch": 8,
                               "fl.learning_rate": 0.05}},
        limits=dict(TINY_FLEET_LIMITS))
