"""Observability tests: sinks, streaming taps, zero-cost-off invariants.

Tier-1 except the distributed shard_map tap test (subprocess,
multi-device — ``slow``).  The streaming contract under test:

* tap ON: every round's telemetry record reaches the sink (in round
  order, via ``io_callback``) and BIT-MATCHES the post-scan
  ``expand_history`` output — one source of truth, two delivery paths;
* tap OFF (``tap=None``): nothing obs-related is traced, so the lowered
  HLO is byte-identical to a build that never imported obs, and the
  simulator reuses the very same compiled scan for tap=None and
  never-tapped calls.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fl import FLSimulator
from repro.data.pipeline import make_federated_digits
from repro.models import build_model
from repro.obs import sinks as obs_sinks
from repro.obs import tap as obs_tap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sim(fleet_size=0, num_samples=300):
    cfg = get_config("mnist_cnn")
    cfg = dataclasses.replace(
        cfg,
        fl=dataclasses.replace(cfg.fl, devices_per_round=4, local_iters=2,
                               learning_rate=0.05),
        train=dataclasses.replace(cfg.train, global_batch=16),
        fleet=dataclasses.replace(cfg.fleet, size=fleet_size))
    model = build_model(cfg)
    store = make_federated_digits(jax.random.PRNGKey(0),
                                  num_samples=num_samples, num_clients=8)
    return model, FLSimulator(model, cfg, store)


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_make_record_stamps_schema_and_validates():
    rec = obs_sinks.make_record("fl_round", 3, {
        "loss": np.float32(0.5), "selected": np.arange(4),
        "nested": {"a": jnp.float32(1.0)}})
    assert (rec["v"], rec["kind"], rec["round"]) == (1, "fl_round", 3)
    assert rec["loss"] == 0.5 and rec["selected"] == [0, 1, 2, 3]
    assert obs_sinks.validate_record(rec) == []
    # round-trips through json
    assert json.loads(json.dumps(rec)) == rec


def test_validate_record_catches_bad_records():
    good = obs_sinks.make_record("fl_round", 0, {"loss": 1.0})
    for mutate in (
            lambda r: r.update(v=2),
            lambda r: r.update(kind=7),
            lambda r: r.update(round=-1),
            lambda r: r.update(loss=float("nan")),
            lambda r: r.update(loss=object())):
        rec = dict(good)
        mutate(rec)
        assert obs_sinks.validate_record(rec), rec


def test_jsonl_sink_streams_valid_lines(tmp_path):
    sink = obs_sinks.JsonlSink(str(tmp_path))
    for t in range(3):
        sink.emit(obs_sinks.make_record("fl_round", t, {"loss": 0.1 * t}))
        # flushed per emit: a tail -f reader sees the line immediately
        with open(sink.path) as f:
            assert len(f.readlines()) == t + 1
    sink.close()
    sink.close()  # idempotent
    with open(sink.path) as f:
        lines = [json.loads(line) for line in f]
    assert [r["round"] for r in lines] == [0, 1, 2]
    assert sink.emitted == 3
    assert all(obs_sinks.validate_record(r) == [] for r in lines)


def test_aggregating_sink_means_and_percentiles():
    sink = obs_sinks.AggregatingSink()
    for t in range(11):
        sink.emit(obs_sinks.make_record("fl_round", t,
                                        {"loss": float(t), "tag": "x"}))
    s = sink.summary()
    assert s["loss"]["n"] == 11
    assert s["loss"]["mean"] == pytest.approx(5.0)
    assert s["loss"]["p50"] == pytest.approx(5.0)
    assert s["loss"]["p90"] == pytest.approx(9.0)
    assert "tag" not in s          # non-numeric keys are not aggregated
    assert "round" not in s        # schema keys are not metrics


def test_console_sink_formats_the_legacy_round_line():
    rec = obs_sinks.make_record("fl_round", 12, {
        "loss": 0.25, "accuracy": 0.875, "survivors": 3})
    line = obs_sinks.ConsoleSink().format(rec)
    assert line == "  round   12 loss=0.2500 acc=0.8750 survivors=3"


def test_multi_sink_fans_out():
    a, b = obs_sinks.RecordingSink(), obs_sinks.RecordingSink()
    multi = obs_sinks.MultiSink(a, b)
    rec = obs_sinks.make_record("fl_round", 0, {"loss": 1.0})
    multi.emit(rec)
    multi.close()
    assert a.records == [rec] and b.records == [rec]


def test_scan_sink_tap_every_keeps_true_round_indices():
    sink = obs_sinks.RecordingSink()
    tap = obs_tap.scan_sink_tap(sink, start_round=4, every=2)
    for _ in range(5):
        tap({"loss": np.float32(0.0)})
    assert [r["round"] for r in sink.records] == [4, 6, 8]


def test_shard0_sink_tap_stamps_rounds_from_payload_not_arrival():
    """The step tap is an UNORDERED io_callback: consecutive async
    steps may arrive out of order, so the record's round must be the
    payload stamp — never a host-side arrival count.  ``every`` keeps
    absolute-index multiples (resume-stable)."""
    sink = obs_sinks.RecordingSink()
    tap = obs_tap.step_sink_tap(sink, kind="train_step", every=2)
    for r in (4, 3, 2, 6):         # arrival order != step order
        tap({"loss": np.float32(r)}, np.int32(r))
    assert [r["round"] for r in sink.records] == [4, 2, 6]
    assert [r["loss"] for r in sink.records] == [4.0, 2.0, 6.0]


# ---------------------------------------------------------------------------
# streaming from the jitted scans (single device, tier-1)
# ---------------------------------------------------------------------------

def test_fleet_scan_tap_streams_records_bitmatching_history():
    """Tap ON over the fleet scan: one record per round arrives at the
    sink (in order, stamped with emit times) and bit-matches the
    ``expand_history`` dicts the same call returns."""
    model, sim = _sim(fleet_size=64)
    params = model.init(jax.random.PRNGKey(1))
    sink = obs_sinks.RecordingSink()
    t0 = time.perf_counter()
    _, hist = sim.run_rounds(params, 4, jax.random.PRNGKey(2),
                             tap=obs_tap.scan_sink_tap(sink))
    t1 = time.perf_counter()
    assert len(sink.records) == len(hist) == 4
    assert all(t0 < te < t1 for te in sink.emit_times)
    assert sink.emit_times == sorted(sink.emit_times)  # round order
    for rec, h in zip(sink.records, hist):
        assert obs_sinks.validate_record(rec) == []
        assert (rec["v"], rec["kind"]) == (1, "fl_round")
        assert rec["round"] == h["round"]
        # the record is the SAME telemetry the history expands — bit-exact
        for key in ("loss", "accuracy", "survivors", "tau_s",
                    "cohort_energy_j", "battery_total_j", "outage_rate",
                    "harvested_j"):
            assert rec[key] == h[key], key
        valid = np.asarray(rec["valid"]) > 0
        assert np.asarray(rec["selected"])[valid].tolist() == h["selected"]


def test_fleet_history_unchanged_by_tap():
    """The streamed tap must not perturb the computation: params and
    history bit-match between tap ON and tap OFF runs."""
    model, sim = _sim(fleet_size=64)
    params = model.init(jax.random.PRNGKey(1))
    fleet0 = sim.fleet_state
    p_off, h_off = sim.run_rounds(params, 3, jax.random.PRNGKey(2))
    sim.fleet_state = fleet0
    p_on, h_on = sim.run_rounds(params, 3, jax.random.PRNGKey(2),
                                tap=obs_tap.scan_sink_tap(
                                    obs_sinks.RecordingSink()))
    assert h_on == h_off
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        p_on, p_off)
    assert max(jax.tree_util.tree_leaves(d)) == 0.0


def test_legacy_scan_tap_streams_records():
    """The non-fleet scan path streams (loss, accuracy, survivors) records
    matching its history."""
    model, sim = _sim(fleet_size=0)
    params = model.init(jax.random.PRNGKey(1))
    sink = obs_sinks.RecordingSink()
    _, hist = sim.run_rounds(params, 3, jax.random.PRNGKey(2),
                             tap=obs_tap.scan_sink_tap(sink))
    assert len(sink.records) == len(hist) == 3
    for rec, h in zip(sink.records, hist):
        assert obs_sinks.validate_record(rec) == []
        assert rec["round"] == h["round"]
        assert rec["loss"] == h["loss"]
        assert rec["accuracy"] == h["accuracy"]
        assert rec["survivors"] == h["survivors"]


def test_train_sink_streams_while_console_logs(capsys):
    model, sim = _sim(fleet_size=64)
    params = model.init(jax.random.PRNGKey(1))
    sink = obs_sinks.RecordingSink()
    _, hist = sim.train(params, 3, jax.random.PRNGKey(2), log_every=1,
                        sink=sink)
    assert len(hist) == 3 and len(sink.records) == 3
    out = capsys.readouterr().out
    assert out.count("round") == 3 and "loss=" in out and "acc=" in out


def test_tap_none_reuses_the_untapped_compile():
    """``tap=None`` and never-tapped calls hit the SAME compiled scan
    (cache key tapped=False) — zero-cost-off by construction at the
    simulator level."""
    model, sim = _sim(fleet_size=64)
    params = model.init(jax.random.PRNGKey(1))
    sim.run_rounds(params, 1, jax.random.PRNGKey(2))
    assert set(sim._fleet_scan_fns) == {(None, False)}
    sim.run_rounds(params, 1, jax.random.PRNGKey(3), tap=None)
    assert set(sim._fleet_scan_fns) == {(None, False)}
    sim.run_rounds(params, 1, jax.random.PRNGKey(4),
                   tap=obs_tap.scan_sink_tap(obs_sinks.RecordingSink()))
    assert set(sim._fleet_scan_fns) == {(None, False), (None, True)}
    assert sim._active_tap is None  # cleared after every call


def test_emit_in_scan_none_is_hlo_byte_identical():
    """Primitive-level zero-cost-off: a scan body calling
    ``emit_in_scan(tel, None)`` lowers to BYTE-IDENTICAL text vs a body
    with no obs call at all; a live tap lowers an extra custom_call."""
    def body_none(c, x):
        tel = {"loss": c}
        obs_tap.emit_in_scan(tel, None)
        return c + x, tel["loss"]

    def body_bare(c, x):
        tel = {"loss": c}
        return c + x, tel["loss"]

    def body_tapped(c, x):
        tel = {"loss": c}
        obs_tap.emit_in_scan(tel, lambda t: None)
        return c + x, tel["loss"]

    xs = jnp.arange(4.0)

    def lower(body):
        return jax.jit(lambda c, xs: jax.lax.scan(body, c, xs)).lower(
            jnp.float32(0.0), xs).as_text()

    assert lower(body_none) == lower(body_bare)
    tapped = lower(body_tapped)
    assert tapped != lower(body_bare)
    assert "custom_call" in tapped or "custom-call" in tapped


# ---------------------------------------------------------------------------
# distributed shard_map tap (subprocess, multi-device)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_tap_all_modes_records_match_metrics():
    """The step tap under ``make_fl_round``: on the flat (2,4) and
    nested (2,2,2) meshes, across all six wire modes, every step streams
    exactly ONE record whose payload bit-matches the step's returned
    metrics — and the tapped round's params are bit-identical to the
    untapped build's."""
    code = """
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.core.fl import make_fl_round
    from repro.data.synthetic import token_batch
    from repro.obs import sinks as obs_sinks
    from repro.obs import tap as obs_tap
    from repro.utils.compat import make_mesh, set_mesh

    for shape, axes in (((2, 4), ("data", "model")),
                        ((2, 2, 2), ("pod", "data", "model"))):
        mesh = make_mesh(shape, axes)
        cfg = reduced(get_config("olmo-1b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = token_batch(jax.random.PRNGKey(1), 12, 32,
                            cfg.model.vocab_size)
        with set_mesh(mesh):
            for mode in ("paper", "int", "packed", "ring", "rsag", "auto"):
                sink = obs_sinks.RecordingSink()
                tap = obs_tap.step_sink_tap(sink, kind="train_step")
                f_off = jax.jit(make_fl_round(model, cfg, mesh,
                                              collective=mode))
                # tapped round fns take a trailing step scalar that
                # stamps the streamed record with its true round index
                f_on = jax.jit(make_fl_round(model, cfg, mesh,
                                             collective=mode, tap=tap))
                p_off, m_off = f_off(params, batch, jax.random.PRNGKey(2))
                p_on, m_on = f_on(params, batch, jax.random.PRNGKey(2),
                                  jnp.int32(7))
                jax.block_until_ready(p_on)
                # exactly one record per step: the callback sits after
                # the shard_map, on the replicated metrics
                assert len(sink.records) == 1, (shape, mode,
                                                len(sink.records))
                rec = sink.records[0]
                assert obs_sinks.validate_record(rec) == []
                assert rec["kind"] == "train_step" and rec["round"] == 7
                assert rec["loss"] == float(m_on["loss"])
                assert rec["survivors"] == float(m_on["survivors"])
                assert (rec["wire_bits_per_param"]
                        == float(m_on["wire_bits_per_param"]))
                assert set(rec["wire_phase_bits_per_param"]) \
                    == set(m_on["wire_phase_bits_per_param"])
                # the tap must not perturb the round
                d = jax.tree_util.tree_map(
                    lambda a, b: float(jnp.abs(
                        a.astype(jnp.float32)
                        - b.astype(jnp.float32)).max()), p_on, p_off)
                assert max(jax.tree_util.tree_leaves(d)) == 0.0, (shape,
                                                                  mode)
                assert float(m_on["loss"]) == float(m_off["loss"])
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# the simulator's host spans (obs.trace.host_span) and its sync counter
# ---------------------------------------------------------------------------

def _sim_host_span_counts(trace_dir):
    """name -> count of the sim/* host spans in a profiler trace, read
    back with ``jax.profiler.ProfileData``."""
    import collections
    import glob
    from jax.profiler import ProfileData
    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    assert pbs, "the profiler wrote no trace"
    counts = collections.Counter()
    for plane in ProfileData.from_file(sorted(pbs)[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("sim/"):
                    counts[event.name] += 1
    return counts


@pytest.mark.parametrize("fleet_size", [64, 0])
@pytest.mark.parametrize("num_samples", [320, 300],
                         ids=["rectangular", "ragged"])
def test_run_rounds_host_spans_count_rounds_and_syncs(tmp_path, fleet_size,
                                                      num_samples):
    """Under ``jax.profiler.trace``, ``run_rounds`` over 2 chunks records
    one ``sim/scan``/``sim/history`` a chunk and one ``sim/sync`` per
    blocking device -> host read: per chunk either one read per stacked
    telemetry key (fleet) or the scan's stats and the round energy
    (legacy).  A rectangular store (8 partitions of 40) draws each
    chunk's inputs in one ``sim/chunk_inputs``, with no client choice
    read back; a ragged one (partitions of 38 and 37) takes the per-draw
    path: one ``sim/round_inputs`` a round with its choice's
    ``sim/sync``, one ``sim/client_batch`` per client minibatch draw and
    one ``sim/stack`` a chunk."""
    from repro.obs import trace as obs_trace
    from repro.population import telemetry as pop_tel
    model, sim = _sim(fleet_size=fleet_size, num_samples=num_samples)
    params = model.init(jax.random.PRNGKey(1))
    chunk, chunks = 2, 2
    params, _ = sim.run_rounds(params, chunk, jax.random.PRNGKey(5))
    with jax.profiler.trace(str(tmp_path)):
        for c in range(chunks):
            params, _ = sim.run_rounds(params, chunk,
                                       jax.random.PRNGKey(10 + c))
    counts = _sim_host_span_counts(str(tmp_path))
    assert set(counts) <= set(obs_trace.SIM_HOST_SPANS)
    rounds = chunk * chunks
    fl = sim.config.fl
    for name in ("sim/scan", "sim/history"):
        assert counts[name] == chunks, name
    # fleet: simulator_round_telemetry's 7 round keys and the fleet extras
    per_chunk = (7 + len(pop_tel.FLEET_METRIC_KEYS)) if fleet_size else 2
    if num_samples == 320:
        assert counts["sim/chunk_inputs"] == chunks
        for name in ("sim/round_inputs", "sim/client_batch", "sim/stack"):
            assert counts[name] == 0, name
        assert counts["sim/sync"] == chunks * per_chunk
    else:
        assert counts["sim/chunk_inputs"] == 0
        assert counts["sim/round_inputs"] == rounds
        assert counts["sim/client_batch"] == (rounds * fl.devices_per_round
                                              * fl.local_iters)
        assert counts["sim/stack"] == chunks
        assert counts["sim/sync"] == rounds + chunks * per_chunk


@pytest.mark.parametrize("fleet_size", [64, 0])
def test_run_rounds_unchanged_by_host_spans(tmp_path, monkeypatch,
                                            fleet_size):
    """The host spans observe and change nothing: params and history of
    ``run_rounds`` bit-match with the spans on (profiler attached or
    not) and with every ``host_span`` replaced by a no-op."""
    import contextlib

    from repro.obs import trace as obs_trace
    model, sim = _sim(fleet_size=fleet_size)
    params = model.init(jax.random.PRNGKey(1))
    fleet0 = sim.fleet_state

    def run():
        sim.fleet_state = fleet0
        return sim.run_rounds(params, 3, jax.random.PRNGKey(2))

    p_on, h_on = run()
    with jax.profiler.trace(str(tmp_path)):
        p_traced, h_traced = run()
    monkeypatch.setattr(obs_trace, "host_span",
                        lambda name: contextlib.nullcontext())
    p_off, h_off = run()
    assert h_on == h_traced == h_off
    for a, b, c in zip(jax.tree_util.tree_leaves(p_on),
                       jax.tree_util.tree_leaves(p_traced),
                       jax.tree_util.tree_leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
