"""Observability: streaming round taps, pluggable sinks, phase tracing.

Three legs (one module each):

* :mod:`repro.obs.tap` — opt-in ``io_callback`` taps that stream each
  round's telemetry dict out of the jitted ``lax.scan`` / distributed
  step WHILE it executes; ``tap=None`` traces nothing (HLO byte-identical to
  a no-obs build).
* :mod:`repro.obs.sinks` — where records land: ``JsonlSink`` (the
  ``--telemetry-dir`` stream), ``AggregatingSink`` (running mean /
  percentiles), ``ConsoleSink`` (the one round-line formatter),
  ``MultiSink`` fan-out, ``RecordingSink`` (tests).
* :mod:`repro.obs.trace` — ``phase_span`` / ``host_span`` named spans
  (select -> power-assign -> quantize-pack-chunk -> per-hop collective
  -> unpack-dequant -> apply) that ``benchmarks/profile_summary.py``
  joins with a ``jax.profiler`` trace into per-phase device time.

Telemetry record schema (version ``sinks.SCHEMA_VERSION`` = 1)
--------------------------------------------------------------

Every record is one JSON object:

  ======================= ======== =========================================
  key                     type     meaning / units
  ======================= ======== =========================================
  ``v``                   int      schema version (1)
  ``kind``                str      ``"fl_round"`` (simulator scan),
                                   ``"train_step"`` (distributed step),
                                   ``"serve_decode"`` (per decode step),
                                   ``"dryrun_combo"`` (one lowered combo)
  ``round``               int      round / step / decode index (0-based
                                   unless resuming; monotonic per stream)
  ======================= ======== =========================================

``fl_round`` payload — the exact ``population.telemetry``
``simulator_round_telemetry`` schema: ``loss``, ``accuracy``,
``selected`` (device-id list), ``valid`` (0/1 mask list), ``survivors``,
``drops``, ``tau_s`` (s), plus the fleet extras ``cohort_energy_j`` /
``energy_budget_j`` / ``harvested_j`` (J), ``selected_valid``,
``battery_total_j`` and ``battery_q{10,50,90}_j`` (J),
``power_q{10,50,90}_w`` (W), ``outage_rate`` / ``outage_target``.

``train_step`` payload — the distributed round's metrics dict: ``loss``,
``survivors``, ``wire_bits_per_param``, nested
``wire_phase_bits_per_param`` (``{"psum": b}`` | ``{"ring_hops": b}`` |
``{"reduce_scatter": b, "all_gather": b}``), plus the same fleet extras
when the population layer is on.

``serve_decode`` payload — ``latency_s`` (per decode step, s) and
``tokens_per_s`` (batch tokens / step latency).

``dryrun_combo`` payload — ``arch``/``shape``/``mesh``/``status`` and,
when OK, ``step`` kind, ``compile_s`` and peak memory estimate.

Records stream one per line (JSONL) via ``JsonlSink``;
``sinks.validate_record`` is the schema gate ``benchmarks/run.py
--check`` runs over a sample stream.
"""
from repro.obs.sinks import (SCHEMA_VERSION, AggregatingSink, ConsoleSink,
                             JsonlSink, MetricsSink, MultiSink,
                             RecordingSink, make_record, to_jsonable,
                             validate_record)
from repro.obs.tap import (emit_in_scan, emit_step, scan_sink_tap,
                           step_sink_tap)
from repro.obs.trace import (FL_PHASES, FLEET_PHASES, WIRE_PHASES,
                             host_span, phase_span)

__all__ = [
    "SCHEMA_VERSION", "AggregatingSink", "ConsoleSink", "JsonlSink",
    "MetricsSink", "MultiSink", "RecordingSink", "make_record",
    "to_jsonable", "validate_record",
    "emit_in_scan", "emit_step", "scan_sink_tap", "step_sink_tap",
    "FL_PHASES", "FLEET_PHASES", "WIRE_PHASES", "host_span", "phase_span",
]
