"""Reduce a ``jax.profiler`` trace to per-phase-span device time.

The obs tracing leg (``repro.obs.trace``) wraps the round's phases in
``jax.named_scope`` spans — those land in the compiled HLO as
``metadata={op_name="jit(f)/jit(main)/<span>/<op>"}`` paths.  A CPU
profiler trace, however, records device events with only the POST-FUSION
instruction name (``args.hlo_op``, e.g. ``multiply_tanh_fusion``) and
the module (``args.hlo_module``) — the span names never appear in the
trace itself.  This module performs the join:

  trace event (hlo_module, hlo_op, dur)
      -> compiled ``as_text()`` line ``%<hlo_op> = ... op_name="<path>"``
      -> OUTERMOST known span on <path>  (``wire/quantize_pack`` beats
         the ``pallas/<kernel>`` nested inside it)
      -> per-span summed microseconds + an attribution coverage ratio.

Two entry points:

  # regenerate the committed span-time artifact (subprocess, forced
  # host devices: the 16x16 dry-run's cohort extent K=16 as mesh (16,1)
  # — same rationale as collective_modes' wall-clock measurement)
  PYTHONPATH=src:. python -m benchmarks.profile_summary --generate

  # summarize an existing capture against its compiled HLO text(s)
  PYTHONPATH=src:. python -m benchmarks.profile_summary \
      --trace DIR_OR_TRACE_GZ --hlo mode=path/to/hlo.txt [...]

The committed artifact lives at
``experiments/dryrun/profile/span_summary_16x16.json`` (next to the raw
PR-6 dry-run capture); ``benchmarks/run.py --check`` (the obs gate)
asserts every mode there attributes >= ``COVERAGE_FLOOR`` of its device
time to the named wire-phase spans.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import subprocess
import sys
import textwrap
from typing import Dict, Iterable, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(ROOT, "experiments", "dryrun", "profile",
                        "span_summary_16x16.json")

#: minimum fraction of a mode's device time the wire-phase spans must
#: explain in the committed artifact (the observability acceptance bar)
COVERAGE_FLOOR = 0.80

#: measurement knobs — mirror collective_modes' wall-clock setup
PROF_D = 421_642                 # the paper's QNN size
PROF_K = 16                      # the 16x16 dry-run's cohort extent
PROF_MODES = ("ring", "rsag", "packed")
PROF_ITERS = 5

_METADATA_RE = re.compile(
    r"%?([\w.\-]+) = .*metadata=\{[^}]*op_name=\"([^\"]+)\"")


# --------------------------------------------------------------------------
# the join: trace events x HLO op_name metadata -> span times
# --------------------------------------------------------------------------

def parse_hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """``as_text()`` -> {instruction name: op_name metadata path}.

    Instruction names are unique module-wide, so one flat map covers the
    fused computations too (the trace references top-level names only).
    """
    return {m.group(1): m.group(2)
            for m in _METADATA_RE.finditer(hlo_text)}


def load_trace_events(trace: str) -> List[Tuple[str, str, float]]:
    """A profile dir or ``*.trace.json.gz`` -> [(module, hlo_op, dur_us)].

    Keeps only complete ("X") events that name an HLO op — the device
    execution rows; host/python rows carry no ``hlo_op`` and are skipped.
    """
    if os.path.isdir(trace):
        hits = sorted(glob.glob(os.path.join(
            trace, "**", "*.trace.json.gz"), recursive=True))
        if not hits:
            raise FileNotFoundError(f"no *.trace.json.gz under {trace}")
        trace = hits[-1]
    opener = gzip.open if trace.endswith(".gz") else open
    with opener(trace, "rt") as f:
        events = json.load(f)["traceEvents"]
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        op = args.get("hlo_op")
        if not op:
            continue
        out.append((args.get("hlo_module", ""), op,
                    float(e.get("dur", 0.0))))
    return out


def outermost_span(path: Optional[str],
                   spans: Iterable[str]) -> Optional[str]:
    """The FIRST known span on an ``op_name`` path (outermost wins —
    ``.../wire/quantize_pack/pallas/quantize_pack_chunk/...`` is
    quantize_pack time, not pallas time)."""
    if not path:
        return None
    best, best_at = None, len(path) + 1
    for span in spans:
        at = path.find("/" + span + "/")
        if at < 0 and path.startswith(span + "/"):
            at = 0
        if 0 <= at < best_at:
            best, best_at = span, at
    return best


def summarize(events: List[Tuple[str, str, float]],
              op_names: Dict[str, Dict[str, str]],
              spans: Iterable[str]) -> Dict[str, dict]:
    """Per-module span attribution.

    ``op_names`` maps each module of interest (trace ``hlo_module``
    value) to its ``parse_hlo_op_names`` map.  Returns, per module:
    ``{"span_us": {span: us}, "other_us", "unprovenanced_us",
    "total_us", "coverage"}``.

    Coverage = attributed / (total - unprovenanced): XLA inserts
    ``copy``/``call``/``broadcast`` instructions with NO ``op_name``
    metadata at all (layout copies at the shard_map boundary, the call
    wrappers whose durations double-count their children) — there is no
    provenance to join them on, so they are reported separately instead
    of silently diluting the ratio.  ``other_us`` is time that DOES
    carry a path but matches no known span — real uninstrumented work,
    and it stays in the denominator.
    """
    spans = tuple(spans)
    out: Dict[str, dict] = {}
    for module, op, dur in events:
        opmap = op_names.get(module)
        if opmap is None:
            continue
        row = out.setdefault(module, {"span_us": {}, "other_us": 0.0,
                                      "unprovenanced_us": 0.0,
                                      "total_us": 0.0})
        row["total_us"] += dur
        path = opmap.get(op)
        if not path:
            row["unprovenanced_us"] += dur
            continue
        span = outermost_span(path, spans)
        if span is None:
            row["other_us"] += dur
        else:
            row["span_us"][span] = row["span_us"].get(span, 0.0) + dur
    for row in out.values():
        attributed = sum(row["span_us"].values())
        denom = row["total_us"] - row["unprovenanced_us"]
        row["coverage"] = round(attributed / denom, 4) if denom else 0.0
        row["span_us"] = {k: round(v, 1)
                          for k, v in sorted(row["span_us"].items(),
                                             key=lambda kv: -kv[1])}
        for k in ("other_us", "unprovenanced_us", "total_us"):
            row[k] = round(row[k], 1)
    return out


# --------------------------------------------------------------------------
# artifact generation (subprocess — forced host devices must not leak)
# --------------------------------------------------------------------------

GEN_CODE = """
import json, os, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.config.base import QuantConfig
from repro.core import aggregation as agg
from repro.utils import compat

K = PROF_K
d = PROF_D
outdir = OUTDIR
mesh = compat.make_mesh((K, 1), ("data", "model"))
delta = jax.random.normal(jax.random.PRNGKey(0), (K, d), jnp.float32) * 0.05
lam = jnp.ones((K,), jnp.float32)
key = jax.random.PRNGKey(7)
fns, modules = {}, {}
with compat.set_mesh(mesh):
    for mode in MODES_TUPLE:
        qcfg = QuantConfig(bits=8, use_pallas=True, pipeline_hops=True)
        plan = agg.make_wire_plan(mode, qcfg, ("data",), (K,))
        def body(dl, l, k, plan=plan):
            r = agg.aggregate(plan, {"w": dl[0]},
                              jnp.float32(1.0 / K), l[0], k)
            return r["w"]
        g = compat.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P("data"), P()),
            out_specs=P(), check_vma=False, axis_names={"data", "model"})
        g.__name__ = "round_" + mode          # distinct hlo_module per mode
        g.__qualname__ = g.__name__
        f = jax.jit(g)
        compiled = f.lower(delta, lam, key).compile()
        with open(os.path.join(outdir, mode + ".hlo.txt"), "w") as fh:
            fh.write(compiled.as_text())
        f(delta, lam, key).block_until_ready()   # warm the exec path
        fns[mode] = f
        modules[mode] = "jit_round_" + mode
    with jax.profiler.trace(os.path.join(outdir, "trace")):
        for mode in MODES_TUPLE:
            for _ in range(PROF_ITERS):
                fns[mode](delta, lam, key).block_until_ready()
print("RESULT " + json.dumps({"modules": modules}))
"""


def _generate(outdir: str, timeout: int = 3000) -> Dict[str, str]:
    """Run the profiled collectives in a subprocess; returns
    {mode: hlo_module name}.  HLO texts + the trace land under outdir."""
    os.makedirs(outdir, exist_ok=True)
    code = (textwrap.dedent(GEN_CODE)
            .replace("PROF_K", repr(PROF_K))
            .replace("PROF_D", repr(PROF_D))
            .replace("PROF_ITERS", repr(PROF_ITERS))
            .replace("OUTDIR", repr(outdir))
            .replace("MODES_TUPLE", repr(PROF_MODES)))
    env = dict(os.environ)
    # a CPU-only rehearsal on forced host devices: never claim a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={PROF_K}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(
            f"profile_summary generate subprocess failed: {r.stderr[-500:]}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])["modules"]


def generate_summary(outdir: str) -> dict:
    """Profile the planned collectives and reduce the capture to the
    committed span-summary record (does not write OUT_JSON itself)."""
    from repro.obs.trace import WIRE_PHASES
    modules = _generate(outdir)
    events = load_trace_events(os.path.join(outdir, "trace"))
    op_names = {}
    for mode, module in modules.items():
        with open(os.path.join(outdir, mode + ".hlo.txt")) as f:
            op_names[module] = parse_hlo_op_names(f.read())
    per_module = summarize(events, op_names, WIRE_PHASES)
    return {
        "what": "per-wire-phase device time of the planned collective "
                "(named_scope spans joined onto the profiler trace)",
        "d": PROF_D, "bits": 8, "data_axis": PROF_K,
        "device_mesh": [PROF_K, 1], "iters": PROF_ITERS,
        "spans": list(WIRE_PHASES),
        "coverage_floor": COVERAGE_FLOOR,
        "modes": {mode: per_module.get(modules[mode],
                                       {"span_us": {}, "other_us": 0.0,
                                        "total_us": 0.0, "coverage": 0.0})
                  for mode in modules},
    }


def check() -> int:
    """Pure-JSON gate over the committed artifact: every mode must exist
    and attribute >= COVERAGE_FLOOR of its device time to the wire-phase
    spans.  Returns the failure count."""
    if not os.path.exists(OUT_JSON):
        print(f"  profile_summary: {os.path.basename(OUT_JSON)} missing "
              f"[REGRESSED]")
        return 1
    with open(OUT_JSON) as f:
        rec = json.load(f)
    failures = 0
    for mode in PROF_MODES:
        row = rec.get("modes", {}).get(mode)
        if row is None:
            print(f"  span_summary.{mode}: missing [REGRESSED]")
            failures += 1
            continue
        ok = row["coverage"] >= COVERAGE_FLOOR
        failures += not ok
        top = next(iter(row["span_us"]), "-")
        print(f"  span_summary.{mode}: coverage={row['coverage']:.1%} "
              f"(floor {COVERAGE_FLOOR:.0%}), top span={top} "
              f"[{'ok' if ok else 'UNDER FLOOR'}]")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--generate", action="store_true",
                    help=f"profile the K={PROF_K} collectives and rewrite "
                         f"{os.path.relpath(OUT_JSON, ROOT)}")
    ap.add_argument("--workdir", default="",
                    help="where --generate keeps the raw capture + HLO "
                         "texts (default: a temp dir, discarded)")
    ap.add_argument("--trace", default="",
                    help="summarize-only: a profile dir or trace.json.gz")
    ap.add_argument("--hlo", nargs="*", default=[],
                    help="summarize-only: module=hlo.txt pairs (module = "
                         "the trace's hlo_module value)")
    ap.add_argument("--check", action="store_true",
                    help="gate the committed artifact's span coverage")
    args = ap.parse_args()
    if args.check:
        n = check()
        if n:
            raise SystemExit(f"{n} span-summary gate(s) failed")
        return
    if args.generate:
        if args.workdir:
            rec = generate_summary(args.workdir)
        else:
            import tempfile
            with tempfile.TemporaryDirectory() as td:
                rec = generate_summary(td)
        os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
        with open(OUT_JSON, "w") as f:
            json.dump(rec, f, indent=1)
        for mode, row in rec["modes"].items():
            print(f"{mode}: coverage={row['coverage']:.1%} "
                  f"total={row['total_us']}us {row['span_us']}")
        print(f"wrote {os.path.relpath(OUT_JSON, ROOT)}")
        return
    if not args.trace:
        ap.error("one of --generate / --trace / --check is required")
    from repro.obs.trace import FL_PHASES, FLEET_PHASES, WIRE_PHASES
    op_names = {}
    for pair in args.hlo:
        module, _, path = pair.partition("=")
        with open(path) as f:
            op_names[module] = parse_hlo_op_names(f.read())
    events = load_trace_events(args.trace)
    spans = WIRE_PHASES + FLEET_PHASES + FL_PHASES
    print(json.dumps(summarize(events, op_names, spans), indent=1))


if __name__ == "__main__":
    main()
