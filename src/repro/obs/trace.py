"""Phase-attributed tracing: named spans over the wire/fleet/simulator
phases.

:func:`phase_span` wraps ``jax.named_scope`` — inside a jitted function
it lands the span path in every enclosed HLO op's
``metadata={op_name="jit(f)/.../<span>/<op>"}``, which is what the
benchmark's trace reduction (``bench/benchkit/trace_reduce.py``) joins
against a ``jax.profiler`` trace (whose device events carry only the
post-fusion HLO op names) to attribute device time per phase.
:func:`host_span` wraps ``jax.profiler.TraceAnnotation`` for host-side
(un-jitted) sections: it writes a named slice into the profiler's own
trace, on the device trace's clock, so an idle gap of the device can be
put down to the host stage that covers it.  With no profiler attached a
host span costs one TraceMe check.

Span names are hierarchical ``area/phase`` strings; the canonical wire
phases (mirroring ``telemetry.wire_phase_split``'s keys) are in
:data:`WIRE_PHASES`, the fleet state-machine phases in
:data:`FLEET_PHASES`, the simulator round's own device phases in
:data:`SIM_PHASES` and its host stages in :data:`SIM_HOST_SPANS`.
Nested spans concatenate (``wire/quantize_pack/pallas/quantize_pack_chunk``)
— the reduction attributes an op to the OUTERMOST known phase on its
path.
"""
from __future__ import annotations

import jax

#: the wire phases of one collective round, in execution order
WIRE_PHASES = (
    "wire/quantize_pack",    # quantize -> pack -> chunk front-end
    "wire/psum",             # one-shot all-reduce (paper/int/packed)
    "wire/ring_hops",        # ring ppermute+accumulate hop loop
    "wire/reduce_scatter",   # rsag scatter phase
    "wire/all_gather",       # rsag gather phase (incl. fused f32 store)
    "wire/unpack_dequant",   # unpack + dequantize back-end
)

#: the fleet round_update state-machine phases, in execution order
FLEET_PHASES = (
    "fleet/advance_channel",
    "fleet/power_assign",
    "fleet/rates_cost",
    "fleet/select",
    "fleet/drop_realize",
    "fleet/energy_ledger",
)

#: the FL round phases outside the wire/fleet areas
FL_PHASES = ("fl/local_steps", "fl/apply")

#: the fleet simulator round's device phases (``core.fl.FLSimulator``);
#: siblings of the fleet/* phases, never enclosing them
SIM_PHASES = (
    "sim/client_update",     # vmapped local steps, delta, quantization
    "sim/aggregate",         # error-aware / naive / reweighted aggregate
    "sim/telemetry",         # round telemetry (fleet-wide quantiles)
)

#: the simulator's host stages (``host_span``), per ``run_rounds`` call.
#: A rectangular store (every partition of one length) draws a chunk's
#: inputs in one compiled call, ``sim/chunk_inputs``; a ragged store takes
#: the per-draw path, ``sim/round_inputs`` with its ``sim/client_batch``
#: draws a round, then ``sim/stack``
SIM_HOST_SPANS = (
    "sim/chunk_inputs",      # a chunk's inputs in one compiled call
    "sim/round_inputs",      # per-draw: one round's choice + batch draws
    "sim/client_batch",      # per-draw: one eager client minibatch draw
    "sim/stack",             # per-draw: stacking a chunk's inputs
    "sim/scan",              # dispatching the jitted round scan
    "sim/history",           # expanding the stacked telemetry
    "sim/sync",              # one blocking device -> host read
)


def phase_span(name: str):
    """A trace-time span: every op traced inside carries ``name`` on its
    HLO ``op_name`` metadata path (works inside jit/scan/shard_map)."""
    return jax.named_scope(name)


def host_span(name: str):
    """A host-side profiler span (``jax.profiler.TraceAnnotation``) for
    un-jitted sections — shows up as a named slice in the trace viewer."""
    return jax.profiler.TraceAnnotation(name)
