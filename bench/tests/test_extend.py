"""A new architecture comes to the harness as new files only: its
configuration names its reference module, and its traffic, limits and
readers are found by name.  The readers may declare spans at any depth and
read the program's counters."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax

import tiny
from benchkit import common, fl_round, work

ROOT = common.ROOT

#: the files a new architecture adds, relative to the checkout
NEW_FILES = {
    "bench/configs/tiny-lm.json": {
        "name": "tiny-lm", "source": "https://huggingface.co/allenai/OLMo-1B",
        "arch": "olmo-1b", "reference": "ref_tiny_lm",
        "model": {"n_layers": 2, "d_model": 128, "n_heads": 4},
        "overrides": tiny.TINY_MODEL, "reduced": []},
    "bench/traffic/tiny_local.json": dict(tiny.fl_cell().traffic),
    "bench/limits/tiny-lm.tiny_local.json": tiny.TINY_FL_LIMITS,
    "bench/benchkit/ref_tiny_lm.py": '''"""A second architecture: the OLMo reference, its FLOPs counted without
the attention's own."""
from benchkit.ref_olmo import bf16_dot, dims, f32_dot, run_rounds  # noqa
from benchkit.work import olmo_param_count as param_count


def round_flops(dims, tokens, seq_len):
    return float(6 * param_count(dims) * tokens)
''',
    "bench/metrics/backward_ms.py": '''"""The local steps' backward pass, a span nested in fl/local_steps."""
from benchkit import readers

SPANS = ("transpose(jvp())",)


def read(ctx):
    return readers.span_in_ms(ctx, SPANS)
''',
    "bench/metrics/survivors.tiny.py": '''"""A program counter: the mean survivors of the window's rounds."""


def read(ctx):
    return ctx["counters"].get("survivors")
''',
    "bench/metrics/flops.tiny.py": '''"""The yardstick's FLOPs of a round, from the architecture module."""


def read(ctx):
    return ctx["work"].get("flops_per_round")
''',
}

CELL = "tiny-lm.tiny_local"
READERS = ("backward_ms", "survivors.tiny", "flops.tiny")


def entries(spec):
    return {(k, e["name"]): e for k in ("configs", "workloads", "end_to_end",
                                        "per_layer") for e in spec[k]}


def add_architecture(spec):
    spec["configs"].append({"name": "tiny-lm",
                            "source": "https://huggingface.co/allenai/OLMo-1B",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "a tiny second architecture"})
    spec["workloads"].append({"name": CELL, "config": "tiny-lm",
                              "traffic": "tiny_local", "chips": 1,
                              "why": "the tiny FL round"})
    for name in READERS:
        spec["per_layer"].append({"name": name, "unit": "1",
                                  "better": "lower", "source": "device_trace",
                                  "layer": "test", "moves": "round_s",
                                  "workloads": [CELL]})


def test_a_new_architecture_is_new_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), tmp_path)
              for d, _, fs in os.walk(tmp_path) for f in fs}
    for rel, body in NEW_FILES.items():
        assert rel not in before
        with open(tmp_path / rel, "w") as f:
            f.write(body if isinstance(body, str) else json.dumps(body))
    spec = common.load_json(tmp_path / "BENCHMARK.json")
    old = entries(spec)
    add_architecture(spec)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    new = entries(common.load_json(tmp_path / "BENCHMARK.json"))
    assert all(new[k] == v for k, v in old.items())

    code = ("import sys; sys.path.insert(0, 'bench'); import run\n"
            "rc = run.main(['--workload', %r, '--seed', '8589934599',\n"
            "               '--seconds', '0.3', '--trace', '1'],\n"
            "              require_tpu=False)\n"
            "print(sys.modules['benchkit.ref_tiny_lm'].__file__)\n"
            "sys.exit(rc)\n" % CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == str(tmp_path / "bench/benchkit/ref_tiny_lm.py")
    out = json.loads(lines[-2])
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == set(tiny.TINY_FL_LIMITS) | {
        "window_compiles"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == set(READERS)
    assert m["backward_ms"] > 0
    assert 0 < m["survivors.tiny"] <= 1
    dims = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "d_ff": 256,
            "vocab_size": 512, "n_layers": 2}
    tokens = tiny.fl_cell().traffic["global_batch"] * \
        tiny.fl_cell().traffic["seq_len"]
    assert m["flops.tiny"] == 6 * work.olmo_param_count(dims) * tokens
    # the copy's new files left the files already there as they were
    for rel in before - {"BENCHMARK.json"}:
        with open(os.path.join(ROOT, rel), "rb") as a, \
                open(tmp_path / rel, "rb") as b:
            assert a.read() == b.read(), rel


def test_window_counters_are_the_step_metrics_means():
    cell = tiny.fl_cell()
    rec = fl_round.run(cell, 3, 0.2, jax.devices()[:1],
                       common.CompileCounter(), time.perf_counter())
    c = rec["counters"]
    assert set(c) == {"loss", "survivors", "wire_bits_per_param"}
    assert c["wire_bits_per_param"] == 8.0
    assert 0 < c["survivors"] <= 1
    assert 0 < c["loss"] < 20
