"""The arithmetic of the per-layer metrics.  Each metric's reader under
``bench/metrics/`` binds one of these; each returns ``None`` where the
trace holds nothing for it to read.  A reader that reads a span at any
depth, or a host span, declares it in its module (``SPANS``,
``HOST_SPANS``) and passes the same names here."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchkit import peaks as peaks_mod
from benchkit import work

WIRE = ("wire/quantize_pack", "wire/psum", "wire/ring_hops",
        "wire/reduce_scatter", "wire/all_gather", "wire/unpack_dequant",
        "fl/apply")
FLEET = ("fleet/advance_channel", "fleet/power_assign", "fleet/rates_cost",
         "fleet/select", "fleet/drop_realize", "fleet/energy_ledger")


def _per_round(ctx, table: Dict[str, float], names) -> Optional[float]:
    hit = [table[n] for n in names if n in table]
    if not hit or not ctx["rounds"]:
        return None
    return sum(hit) / ctx["rounds"]


def span_seconds_per_round(ctx: Dict[str, Any], spans) -> Optional[float]:
    return _per_round(ctx, ctx["span_s"], spans)


def span_ms(ctx, spans) -> Optional[float]:
    s = span_seconds_per_round(ctx, spans)
    return None if s is None else s * 1e3


def span_in_ms(ctx, spans) -> Optional[float]:
    """Device ms per round of the ops under declared ``spans``, at any
    depth of their path."""
    s = _per_round(ctx, ctx["span_in_s"], spans)
    return None if s is None else s * 1e3


def host_span_ms(ctx, names) -> Optional[float]:
    """Host ms per round inside the window of declared host spans."""
    s = _per_round(ctx, ctx["host_span_s"], names)
    return None if s is None else s * 1e3


def host_span_count(ctx, names) -> Optional[float]:
    """Declared host spans per round inside the window."""
    return _per_round(ctx, ctx["host_span_n"], names)


def local_steps_ms(ctx):
    return span_ms(ctx, ("fl/local_steps",))


def wire_ms(ctx):
    return span_ms(ctx, WIRE)


def fleet_ms(ctx):
    return span_ms(ctx, FLEET)


def wire_roofline(ctx):
    """Least time of the aggregation's interface bytes over its device
    time per round, in %."""
    s = span_seconds_per_round(ctx, WIRE)
    w = ctx["work"]
    if not s or "wire_hbm_bytes" not in w:
        return None
    pk = peaks_mod.peaks_for(ctx["device_kind"])
    least = work.least_seconds(w["wire_hbm_bytes"], w["wire_ici_bytes"],
                               0.0, pk)
    return least / s * 100.0


def fleet_roofline(ctx):
    """Reading and writing the fleet state once, at HBM peak, over the
    fleet's device time per round, in %."""
    s = span_seconds_per_round(ctx, FLEET)
    w = ctx["work"]
    if not s or "fleet_bytes" not in w:
        return None
    pk = peaks_mod.peaks_for(ctx["device_kind"])
    return work.least_seconds(w["fleet_bytes"], 0.0, 0.0, pk) / s * 100.0


def step_mfu(ctx):
    """Model FLOPs per round over (traced round time x chips x bf16
    peak), in %."""
    w = ctx["work"]
    if "flops_per_round" not in w or not ctx["rounds"]:
        return None
    pk = peaks_mod.peaks_for(ctx["device_kind"])
    round_s = ctx["window_s"] / ctx["rounds"]
    return w["flops_per_round"] / (round_s * ctx["chips"]
                                   * pk.bf16_flops) * 100.0


def idle_share(ctx):
    """1 - busy / window on the device, averaged over chips, in %."""
    if ctx["window_s"] <= 0:
        return None
    return (1.0 - ctx["busy_s"] / ctx["window_s"]) * 100.0
