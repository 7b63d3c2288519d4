"""Collective-payload comparison across all six wire formats:

  paper  — f32 psum (faithful; n-bit payload simulated only)
  int    — integer codes in the smallest int container (int8/16/32)
  packed — codes bit-packed into dense uint32 words (wire ≈ payload_bits)
  ring   — native-width ppermute ring, no guard bits (wire = d·n per hop)
  rsag   — reduce-scatter + all-gather, growing lane widths
           (wire ≈ 2·d·(n+⌈log2 K⌉) regardless of K)
  auto   — resolved at trace time to the byte-minimal concrete mode
           (ring on 2x4, packed on 16x16)

Each mode is lowered on the selected mesh and the post-SPMD HLO's
collective bytes are parsed; the per-mode bytes land in
``BENCH_collective_modes.json`` next to this file (one entry per mesh,
existing entries preserved) so the wire-size trajectory is tracked across
PRs.  ``run.py --check`` recomputes the debug-mesh entry, fails on any
byte regression, and — for EVERY committed entry — fails if "auto" is
recorded as resolving to a mode that is not minimal by the entry's own
``wire_bits_per_param`` (the honest metric; see the CAVEAT below for why
raw HLO bytes cannot be compared across one-shot and scanned modes), or
if rsag does not beat the ring's HLO bytes on a large-cohort (K >= 16)
mesh.

Meshes:
  2x4   (default) — the 8-device debug mesh, data axis K=2
  16x16           — the production dry-run, data axis K=16 (256 forced
                    host devices; lowering only, minutes on CPU)

CAVEAT: the HLO parser counts a scanned collective ONCE, not per loop trip
(the same under-count utils/flops.py documents for flops) — so the ring's
``collective_bytes`` is its per-hop cost and rsag's is one hop per
equal-lane scan group (O(log K) groups).  ``wire_bits_per_param`` is the
honest per-device total (hops x lane width): at K=16 the ring ships
15x8=120 bits/param, rsag 28.5, and the one-shot packed psum (16
bits/param) wins — which is exactly what "auto" picks there; the ring's
regime is the small-K cohort axes of the hierarchical meshes.

WALL-CLOCK entries (this PR): alongside the lowered-bytes rows, each mesh
entry carries timed executions of the planned collective itself — a
synthetic d = 421 642 delta sharded over the cohort axis, shard_map'd
``agg.aggregate`` with ``use_pallas=True``, timed warmed-up /
block_until_ready / median-of-N (benchmarks/common.time_stats) for the
hop modes (ring, rsag) and packed, each under BOTH hop schedules
(``pipeline_hops`` True/False — the pre-pipelining sequential baseline).  The
wall-clock subprocess forces only the COHORT extent as devices (mesh
(K, 1): K=2 for "2x4", K=16 for "16x16") — the collective spans only the
data axis, and forcing 256 host devices onto one core would time the
interpreter's device loop, not the schedule.  ``run.py --check`` gates:
pipelined <= sequential for the hop modes (the double-buffered schedule
must never lose), a +-25% invariance band for packed (hop-free, schedule
can't matter), and a re-measured budget on the debug mesh (auto's
resolved mode within WALL_MARGIN of its committed median — machine-
relative, like fleet_scale's budget).

Runs in a subprocess so the forced device count never leaks into other
benchmarks (the brief: only the dry-run sees >1 device globally).

For a spans-level view of the 16x16 production mesh, a ``jax.profiler``
trace of the full dry-run sweep (512 forced host devices, lower+compile)
is committed at ``experiments/dryrun/profile/`` — regenerate via
``python -m repro.launch.dryrun --profile-dir experiments/dryrun/profile``
and open the ``.trace.json.gz`` in Perfetto (see the README next to it).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit
from repro.config.base import COLLECTIVE_CHOICES  # jax-free source of truth

MODES = COLLECTIVE_CHOICES
CONCRETE = tuple(m for m in MODES if m != "auto")
QUANTIZED = tuple(m for m in CONCRETE if m != "paper")
MESHES = {"2x4": (2, 4), "16x16": (16, 16)}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_collective_modes.json")

# wall-clock measurement knobs (see the module docstring)
WALL_D = 421_642                  # the paper's QNN size
WALL_MODES = ("ring", "rsag", "packed")
HOP_MODES = ("ring", "rsag")      # schedules differ only where hops exist
WALL_BAND = 1.25                  # packed pipelined/sequential invariance
WALL_MARGIN = 8.0                 # re-measured budget vs committed median

CODE = """
import dataclasses, json, time, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.core import aggregation as agg
from repro.core.fl import fl_data_axes, make_fl_round
from repro.data.synthetic import token_batch
from repro.utils.compat import make_mesh, set_mesh
from repro.utils.hlo import collective_bytes

mesh_shape = MESH_SHAPE
mesh = make_mesh(mesh_shape, ("data","model"))
cfg = reduced(get_config("olmo-1b"))
model = build_model(cfg)
bs = 6 * mesh_shape[0]  # 2 samples per local iter per cohort (12 on 2x4)
batch = token_batch(jax.random.PRNGKey(1), bs, 32, cfg.model.vocab_size)
p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
# the same cohort the lowered round plans over (not assumed single-axis)
sizes = tuple(int(mesh.shape[a]) for a in fl_data_axes(mesh, cfg))
out = {"auto_resolves_to": agg.resolve_auto(cfg.quant, sizes)}
with set_mesh(mesh):
    for mode in MODES_TUPLE:
        t0 = time.perf_counter()
        f = jax.jit(make_fl_round(model, cfg, mesh, collective=mode))
        txt = f.lower(p, batch, rng).compile().as_text()
        cb = collective_bytes(txt)
        out[mode] = {"collective_bytes": cb["total"],
                     "wire_bits_per_param": agg.wire_bits_per_param(
                         mode, cfg.quant, sizes),
                     "lower_compile_us": (time.perf_counter()-t0)*1e6}
print("RESULT " + json.dumps(out))
"""


WALL_CODE = """
import dataclasses, json, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from benchmarks.common import time_stats
from repro.config.base import QuantConfig
from repro.core import aggregation as agg
from repro.utils import compat

K = COHORT_K
d = WALL_D
mesh = compat.make_mesh((K, 1), ("data", "model"))
delta = jax.random.normal(jax.random.PRNGKey(0), (K, d), jnp.float32) * 0.05
lam = jnp.ones((K,), jnp.float32)
key = jax.random.PRNGKey(7)
out = {"auto_mode": agg.resolve_auto(QuantConfig(bits=8), (K,)),
       "modes": {}}
with compat.set_mesh(mesh):
    for mode in MODES_TUPLE:
        row = {}
        for schedule in ("pipelined", "sequential"):
            qcfg = QuantConfig(bits=8, use_pallas=True,
                               pipeline_hops=(schedule == "pipelined"))
            plan = agg.make_wire_plan(mode, qcfg, ("data",), (K,))
            def body(dl, l, k, plan=plan):
                # one cohort shard: (1, d) block -> flat leaf, scalar lam
                r = agg.aggregate(plan, {"w": dl[0]},
                                  jnp.float32(1.0 / K), l[0], k)
                return r["w"]
            f = jax.jit(compat.shard_map(
                body, mesh=mesh,
                in_specs=(P("data"), P("data"), P()), out_specs=P(),
                check_vma=False, axis_names={"data", "model"}))
            st = time_stats(f, delta, lam, key, warmup=2, iters=5)
            row[schedule + "_us"] = round(st["median_us"], 1)
            row[schedule + "_iqr_us"] = round(st["iqr_us"], 1)
        row["speedup"] = round(row["sequential_us"] / row["pipelined_us"], 4)
        out["modes"][mode] = row
print("RESULT " + json.dumps(out))
"""


def _subprocess_env(devices: int) -> dict:
    env = dict(os.environ)
    # a CPU-only rehearsal on forced host devices: never claim a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # src for repro.*, the repo root for benchmarks.common (the shared
    # timing harness the wall-clock subprocess reuses)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def _run_result(code: str, env: dict, timeout: int, what: str) -> dict:
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"collective_modes {what} subprocess failed: "
                           f"{r.stderr[-400:]}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])


def _measure(mesh_key: str, timeout: int = 3000) -> dict:
    shape = MESHES[mesh_key]
    code = (textwrap.dedent(CODE).replace("MESH_SHAPE", repr(shape))
            .replace("MODES_TUPLE", repr(MODES)))
    return _run_result(code, _subprocess_env(shape[0] * shape[1]),
                       timeout, mesh_key)


def _measure_wall(mesh_key: str, timeout: int = 3000) -> dict:
    """Timed execution of the planned collective on mesh (K, 1), K = the
    cohort extent of ``mesh_key`` (the collective only spans the data
    axis; see the module docstring for why the model axis is not forced)."""
    K = MESHES[mesh_key][0]
    code = (textwrap.dedent(WALL_CODE).replace("COHORT_K", repr(K))
            .replace("WALL_D", repr(WALL_D))
            .replace("MODES_TUPLE", repr(WALL_MODES)))
    res = _run_result(code, _subprocess_env(K), timeout, f"wall:{mesh_key}")
    res.update(d=WALL_D, bits=8, data_axis=K, device_mesh=[K, 1])
    return res


def _load() -> dict:
    if os.path.exists(OUT_JSON):
        with open(OUT_JSON) as f:
            return json.load(f)
    return {}


def _store(mesh_key: str, res: dict, wall: dict | None = None) -> None:
    record = _load()
    record["arch"] = "olmo-1b (reduced)"
    entries = record.setdefault("entries", {})
    # legacy flat schema (PR 1): migrate its debug entry
    if "bytes_per_mode" in record:
        entries.setdefault("2x4", {
            "mesh": record.pop("mesh", [2, 4]),
            "bytes_per_mode": record.pop("bytes_per_mode")})
    prev_wall = entries.get(mesh_key, {}).get("wall_clock")
    entries[mesh_key] = {
        "mesh": list(MESHES[mesh_key]),
        "bytes_per_mode": {m: res[m]["collective_bytes"] for m in MODES},
        "wire_bits_per_param": {m: round(res[m]["wire_bits_per_param"], 4)
                                for m in MODES},
        "auto_resolves_to": res["auto_resolves_to"],
    }
    if wall is not None or prev_wall is not None:
        entries[mesh_key]["wall_clock"] = wall if wall is not None else prev_wall
    with open(OUT_JSON, "w") as f:
        json.dump(record, f, indent=1)


def run(mesh_key: str = "2x4") -> None:
    try:
        res = _measure(mesh_key)
        wall = _measure_wall(mesh_key)
    except Exception as e:  # noqa: BLE001 - benchmark must not crash the suite
        emit("collective_modes", 0.0, f"FAIL:{str(e)[-160:]}")
        return
    cb_paper = res["paper"]["collective_bytes"]
    for mode in MODES:
        cb = res[mode]["collective_bytes"]
        reduction = 1.0 - cb / cb_paper
        extra = (f";resolves_to={res['auto_resolves_to']}"
                 if mode == "auto" else "")
        emit(f"collective_{mode}_wire_{mesh_key}",
             res[mode]["lower_compile_us"],
             f"collective_bytes={cb};bits_per_param="
             f"{res[mode]['wire_bits_per_param']:.2f};"
             f"reduction_vs_paper={reduction:.2%}{extra}")
    for mode, row in wall["modes"].items():
        emit(f"collective_{mode}_wall_{mesh_key}", row["pipelined_us"],
             f"sequential_us={row['sequential_us']};"
             f"pipeline_speedup={row['speedup']};d={wall['d']};"
             f"data_axis={wall['data_axis']}")
    _store(mesh_key, res, wall)
    emit("collective_modes_json", 0.0,
         f"wrote={os.path.basename(OUT_JSON)}:{mesh_key}")


def _check_auto_minimal(entries: dict) -> int:
    """Gate: in EVERY committed entry "auto" must resolve to the mode with
    the minimal ``wire_bits_per_param`` — the honest per-device total, NOT
    the raw HLO bytes, which under-count scanned collectives (the ring's
    120 bits/param shows as one hop of bytes; see the module caveat) — and
    on large-cohort meshes (data axis >= 16) rsag's HLO bytes must beat
    the per-hop ring's.  Pure-JSON checks — no recompute, so they cover
    every mesh cheaply."""
    failures = 0
    for key, entry in entries.items():
        wire = entry.get("wire_bits_per_param", {})
        resolved = entry.get("auto_resolves_to")
        if resolved is None or "auto" not in wire:
            print(f"  {key}: no auto entry committed yet [REGRESSED]")
            failures += 1
            continue
        best = min(wire[m] for m in QUANTIZED if m in wire)
        ok = wire.get(resolved, float("inf")) <= best
        status = "ok" if ok else "NOT WIRE-BIT-MINIMAL"
        failures += not ok
        print(f"  {key}: auto -> {resolved} "
              f"({wire.get(resolved)} bits/param, min={best}) [{status}]")
        bpm = entry.get("bytes_per_mode", {})
        if entry.get("mesh", [0])[0] >= 16 and {"rsag", "ring"} <= set(bpm):
            ok = bpm["rsag"] < bpm["ring"]
            failures += not ok
            print(f"  {key}: rsag bytes {bpm['rsag']} vs ring {bpm['ring']} "
                  f"[{'ok' if ok else 'RSAG DOES NOT BEAT RING'}]")
    return failures


def _check_wall_committed(entries: dict) -> int:
    """Pure-JSON wall-clock gates over EVERY committed entry: the
    double-buffered schedule must not lose to sequential on the hop modes
    (that is the tentpole's whole point), and packed — hop-free, so the
    knob cannot matter — must sit inside the WALL_BAND invariance band.
    Diff-style report names (mesh, mode, metric) for each line."""
    failures = 0
    for key, entry in entries.items():
        wall = entry.get("wall_clock")
        if wall is None:
            print(f"  wall_clock[{key}]: no committed wall-clock entry "
                  f"[REGRESSED]")
            failures += 1
            continue
        for mode in HOP_MODES:
            row = wall["modes"].get(mode)
            if row is None:
                print(f"  wall_clock[{key}].{mode}: missing [REGRESSED]")
                failures += 1
                continue
            ok = row["pipelined_us"] <= row["sequential_us"]
            failures += not ok
            print(f"  wall_clock[{key}].{mode}: pipelined_us="
                  f"{row['pipelined_us']} sequential_us="
                  f"{row['sequential_us']} (speedup {row['speedup']}x) "
                  f"[{'ok' if ok else 'PIPELINE LOSES'}]")
        row = wall["modes"].get("packed")
        if row is not None:
            ratio = row["sequential_us"] / row["pipelined_us"]
            ok = 1.0 / WALL_BAND <= ratio <= WALL_BAND
            failures += not ok
            print(f"  wall_clock[{key}].packed: schedule ratio "
                  f"{ratio:.3f} (band 1/{WALL_BAND}..{WALL_BAND}) "
                  f"[{'ok' if ok else 'NOT SCHEDULE-INVARIANT'}]")
    return failures


def _check_wall_budget(entry: dict, mesh_key: str) -> int:
    """Re-measured gate on the debug mesh: auto's resolved mode must still
    run pipelined <= sequential (with the band where hop-free), and its
    pipelined median must stay within WALL_MARGIN of the committed value
    (machine-relative budget, the fleet_scale pattern — absolute CPU
    timings are not portable across hosts)."""
    wall = entry.get("wall_clock")
    auto_mode = entry.get("auto_resolves_to")
    if wall is None or auto_mode not in wall.get("modes", {}):
        print(f"  wall_clock[{mesh_key}]: committed entry lacks auto mode "
              f"{auto_mode!r} [REGRESSED]")
        return 1
    got = _measure_wall(mesh_key)["modes"]
    failures = 0
    row, want = got[auto_mode], wall["modes"][auto_mode]
    band = 1.0 if auto_mode in HOP_MODES else WALL_BAND
    ok = row["pipelined_us"] <= row["sequential_us"] * band
    failures += not ok
    print(f"  wall_clock[{mesh_key}].{auto_mode} (auto, re-measured): "
          f"pipelined_us={row['pipelined_us']} sequential_us="
          f"{row['sequential_us']} [{'ok' if ok else 'PIPELINE LOSES'}]")
    budget = want["pipelined_us"] * WALL_MARGIN
    ok = row["pipelined_us"] <= budget
    failures += not ok
    print(f"  wall_clock[{mesh_key}].{auto_mode}.pipelined_us: "
          f"committed={want['pipelined_us']} recomputed="
          f"{row['pipelined_us']} budget={budget:.1f} "
          f"[{'ok' if ok else 'OVER BUDGET'}]")
    return failures


def check(mesh_key: str = "2x4") -> int:
    """Regression gate: recompute ``bytes_per_mode`` for ``mesh_key`` and
    compare with the committed JSON, re-measure the wall-clock budget for
    auto's resolved mode there, then run the pure-JSON gates (auto
    wire-bit-minimality + wall-clock schedule wins) over every committed
    entry.  Returns the failure count (0 = pass)."""
    committed = _load().get("entries", {})
    entry = committed.get(mesh_key)
    if entry is None:
        print(f"collective_modes --check: no committed entry for {mesh_key}")
        return 1
    res = _measure(mesh_key)
    failures = 0
    for mode in MODES:
        want = entry["bytes_per_mode"].get(mode)
        got = res[mode]["collective_bytes"]
        if want is None:
            print(f"  {mode}: NEW (no committed bytes), got {got}")
            continue
        status = "ok" if got <= want else "REGRESSED"
        failures += got > want
        print(f"  {mode}: committed={want} recomputed={got} [{status}]")
    want_auto = entry.get("auto_resolves_to")
    got_auto = res["auto_resolves_to"]
    if want_auto is not None and got_auto != want_auto:
        print(f"  auto: committed resolution {want_auto!r} != recomputed "
              f"{got_auto!r} [REGRESSED]")
        failures += 1
    failures += _check_auto_minimal(committed)
    failures += _check_wall_committed(committed)
    failures += _check_wall_budget(entry, mesh_key)
    return failures


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="2x4", choices=sorted(MESHES))
    ap.add_argument("--check", action="store_true",
                    help="compare recomputed bytes against the committed "
                         "JSON + the auto byte-minimality gate")
    args = ap.parse_args()
    if args.check:
        n = check(args.mesh)
        if n:
            raise SystemExit(f"{n} collective mode(s) regressed")
    else:
        run(args.mesh)
