"""The harness is driven by data: every cell, traffic mix, configuration,
limit set and per-layer reader is a file found by name."""
import importlib.util
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchkit import common

ROOT = common.ROOT
SPEC = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in SPEC["end_to_end"])
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m["workloads"]) <= cells


def test_every_cell_loads_from_its_files():
    four = 0
    pairs = set()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = common.load_cell(w["name"], SPEC)
        assert importlib.util.find_spec("benchkit." + cell.traffic["driver"])
        assert cell.limits
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
    assert len(pairs) == len(SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_every_per_layer_metric_has_a_reader():
    sys.path.insert(0, common.BENCH_DIR)
    import run
    for m in SPEC["per_layer"]:
        mod = run.load_reader(m["name"])
        assert callable(mod.read)
        for key in ("SPANS", "HOST_SPANS"):
            assert all(isinstance(s, str) and s
                       for s in getattr(mod, key, ())), (m["name"], key)


def test_each_cell_reports_an_end_to_end_metric_besides_setup():
    for w in SPEC["workloads"]:
        e2e = [m for m in SPEC["end_to_end"] if m["name"] != "setup_s"
               and w["name"] in m.get("workloads", [w["name"]])]
        per = [m for m in SPEC["per_layer"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert e2e and per


def test_configuration_files_state_their_source_and_cuts():
    for c in SPEC["configs"]:
        data = common.load_json(os.path.join(ROOT, c["file"]))
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_every_fl_round_configuration_names_its_architecture():
    from benchkit import fl_round
    for w in SPEC["workloads"]:
        cell = common.load_cell(w["name"], SPEC)
        if cell.traffic["driver"] != "fl_round":
            continue
        mod = fl_round.arch(cell)
        assert os.path.dirname(mod.__file__) == os.path.join(
            common.BENCH_DIR, "benchkit")
        for name in fl_round.ARCH_API:
            assert callable(getattr(mod, name)), (cell.config_name, name)


@pytest.mark.parametrize("sizes,ok", [
    ({"n_layers": 24, "moe.num_experts": 32, "moe.experts_per_token": 8},
     True),
    ({"moe.num_experts": 64}, False),
    ({"n_layers": 27}, False),
])
def test_check_model_reads_dotted_keys(sizes, ok):
    from repro.configs import get_config
    cfg = get_config("granite-moe-1b-a400m")
    if ok:
        common.check_model(cfg, sizes)
    else:
        with pytest.raises(ValueError, match=re.escape(next(iter(sizes)))):
            common.check_model(cfg, sizes)


def test_without_a_tpu_the_run_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_without_the_program_the_run_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3])
def test_large_seeds_give_distinct_keys(seed):
    from benchkit import data
    a = data.seed_key(seed)
    b = data.seed_key(seed + 1)
    assert (a != b).any()
    assert (data.seed_key(seed) == a).all()
