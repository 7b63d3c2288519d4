"""Streaming-telemetry overhead gate: the in-scan tap must be ~free.

The obs tentpole's bargain is "streaming rounds for (almost) nothing":
an ``io_callback`` tap inside the fleet scan body ships every round's
telemetry to host sinks WHILE the scan runs, and when it is off the HLO
is byte-identical (test_obs.py pins that).  This benchmark prices the
ON side: the same ``FLSimulator.run_rounds`` fleet scan, A/B timed with
the shared harness (``common.time_stats`` — warmup, ``block_until_ready``
fences, median/IQR) with the tap off vs on.  The tap lands records in an
in-memory :class:`repro.obs.sinks.RecordingSink` so the measurement
prices the callback machinery, not disk I/O.

``run.py --check`` runs :func:`check`:

* the median of the INTERLEAVED per-pair on/off wall-clock ratios must
  stay <= ``OVERHEAD_BAND`` — the <=5%% streaming-overhead acceptance
  bar.  Pairing (off then on inside each iteration, ratio per pair)
  makes the gate immune to background-load drift, which two separate
  ``time_stats`` series are not;
* a real ``JsonlSink`` sample stream written to a temp dir must yield
  one valid record per round (``sinks.validate_record`` — the schema
  gate) with bit-exact loss/accuracy vs the returned history.

Span coverage is not gated here: the benchmark's trace reduction
(``bench/benchkit/trace_reduce.py``) reports it from a chip trace.
"""
from __future__ import annotations

import dataclasses
import json
import tempfile

from benchmarks.common import emit, time_stats

#: tap-on median must stay within this factor of tap-off (the 5% bar)
OVERHEAD_BAND = 1.05

#: fleet-sim measurement knobs (small: the gate times tap overhead, not
#: the model — a bigger model would only hide the callback cost)
FLEET_SIZE = 200
ROUNDS = 4


def _fleet_sim():
    """A small fleet-mode FLSimulator (mnist_cnn, the test harness's
    shape: 4 devices/round, 2 local iters, digits store)."""
    import jax
    from repro.configs import get_config
    from repro.core.fl import FLSimulator
    from repro.data.pipeline import make_federated_digits
    from repro.models import build_model

    cfg = get_config("mnist_cnn")
    cfg = dataclasses.replace(
        cfg,
        fl=dataclasses.replace(cfg.fl, devices_per_round=4, local_iters=2,
                               learning_rate=0.05),
        train=dataclasses.replace(cfg.train, global_batch=16),
        fleet=dataclasses.replace(cfg.fleet, size=FLEET_SIZE))
    model = build_model(cfg)
    store = make_federated_digits(jax.random.PRNGKey(0), num_samples=300,
                                  num_clients=8)
    return model, FLSimulator(model, cfg, store)


def _setup():
    """Compiled-and-warm (run_off, run_on, recording) closures over one
    shared sim — run_on's records land in ``recording``."""
    import jax
    from repro.obs import sinks as obs_sinks
    from repro.obs import tap as obs_tap

    _, sim = _fleet_sim()
    params = sim.model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(2)
    fleet0 = sim.fleet_state

    def run_off():
        sim.fleet_state = fleet0
        return sim.run_rounds(params, ROUNDS, rng)

    recording = obs_sinks.RecordingSink()

    def run_on():
        sim.fleet_state = fleet0
        recording.records.clear()
        recording.emit_times.clear()
        tap = obs_tap.scan_sink_tap(recording)
        return sim.run_rounds(params, ROUNDS, rng, tap=tap)

    run_off()                      # compile both variants out of band
    run_on()
    return run_off, run_on, recording


def _measure():
    """Returns (off_stats, on_stats, records, history) — A/B of the same
    scan, plus the tap-on records for the schema/bit-match checks."""
    run_off, run_on, recording = _setup()
    _, history = run_on()
    off = time_stats(run_off, warmup=1, iters=5)
    on = time_stats(run_on, warmup=1, iters=5)
    return off, on, list(recording.records), history


def _paired_ratios(iters: int = 5):
    """Interleaved per-pair on/off wall-clock ratios (plus the last on-run's
    records and history).  Pairing makes the gate drift-immune: background
    machine load hits both halves of a pair about equally, where two
    back-to-back ``time_stats`` series let a load shift land entirely on
    one side (observed 8%% false overhead under a concurrent test run)."""
    import time

    import jax

    run_off, run_on, recording = _setup()
    ratios = []
    result = None
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run_off())
        t1 = time.perf_counter()
        result = jax.block_until_ready(run_on())
        t2 = time.perf_counter()
        ratios.append((t2 - t1) / (t1 - t0))
    return sorted(ratios), list(recording.records), result[1]


def run() -> None:
    try:
        off, on, records, history = _measure()
    except Exception as e:  # noqa: BLE001 - benchmark must not crash the suite
        emit("obs_overhead", 0.0, f"FAIL:{str(e)[-160:]}")
        return
    ratio = on["median_us"] / off["median_us"]
    emit("obs_tap_off", off["median_us"],
         f"iqr_us={off['iqr_us']:.1f};rounds={ROUNDS};fleet={FLEET_SIZE}")
    emit("obs_tap_on", on["median_us"],
         f"iqr_us={on['iqr_us']:.1f};overhead={ratio - 1.0:+.2%};"
         f"records={len(records)}")


def check() -> int:
    """The two obs gates (see the module docstring); returns failures."""
    from repro.obs import sinks as obs_sinks
    from repro.obs import tap as obs_tap

    failures = 0
    ratios, records, history = _paired_ratios()
    # 1) tap overhead within the band: median of the INTERLEAVED per-pair
    #    on/off ratios (drift-immune — see _paired_ratios)
    median = ratios[len(ratios) // 2]
    ok = median <= OVERHEAD_BAND
    failures += not ok
    print(f"  obs_overhead: paired on/off ratio median={median:.3f} "
          f"(range {ratios[0]:.3f}..{ratios[-1]:.3f}, {len(ratios)} pairs, "
          f"band {OVERHEAD_BAND}) [{'ok' if ok else 'TAP TOO COSTLY'}]")
    # 2) streamed records: one per round, schema-valid, bit-matching the
    #    post-scan history (through a REAL JsonlSink round-trip)
    with tempfile.TemporaryDirectory() as td:
        sink = obs_sinks.JsonlSink(td)
        for rec in records:
            sink.emit(rec)
        sink.close()
        with open(sink.path) as f:
            lines = [json.loads(line) for line in f]
    bad = sum(bool(obs_sinks.validate_record(r)) for r in lines)
    match = (len(lines) == ROUNDS == len(history)
             and all(r["round"] == h["round"]
                     and r["loss"] == h["loss"]
                     and r["accuracy"] == h["accuracy"]
                     for r, h in zip(lines, history)))
    ok = bad == 0 and match
    failures += not ok
    print(f"  obs_records: {len(lines)} jsonl records, {bad} schema "
          f"errors, history bit-match={match} "
          f"[{'ok' if ok else 'STREAM INVALID'}]")
    return failures


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    if args.check:
        n = check()
        if n:
            raise SystemExit(f"{n} obs gate(s) failed")
    else:
        run()
