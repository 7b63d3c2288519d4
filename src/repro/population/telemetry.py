"""Structured per-round telemetry — the one place round metrics are built.

Two consumers share the schema:

* the distributed FL round (``core.fl.make_fl_round``): a flat metrics
  dict per step, now including the per-phase wire split
  ``wire_phase_bits_per_param`` (e.g. the rsag collective's
  reduce_scatter / all_gather legs) next to the total
  ``wire_bits_per_param`` — so energy/latency accounting can charge
  phases with different radio duty cycles separately
  (``energy.uplink_phase_energy_j``);
* the fleet simulator scan (``FLSimulator.run_rounds``): a stacked
  telemetry pytree (one leading round axis) expanded host-side by
  :func:`expand_history` into the same per-round history dicts ``train``
  always produced, plus the fleet extras (selected cohort, realized
  drops, battery quantiles, realized cohort energy/latency).

Everything returned by the ``*_metrics`` builders is jnp (scan-stackable,
shard_map-compatible); phase values are trace-time constants.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.obs import trace as obs_trace

PyTree = Any

#: battery percentiles reported each round
BATTERY_QUANTILES = (10.0, 50.0, 90.0)


def wire_phase_split(plan: "agg.WirePlan") -> Dict[str, float]:
    """The collective's per-phase wire bits/param (python floats).

    Delegates to ``aggregation.wire_phase_bits_per_param`` on the plan's
    requested mode ("auto" resolves inside) — one-shot psum modes report
    {"psum": b}, the ring {"ring_hops": b}, rsag the
    {"reduce_scatter": b_rs, "all_gather": b_ag} split.  Values sum to
    ``plan.wire_bits``.
    """
    return agg.wire_phase_bits_per_param(plan.mode, plan.quant,
                                         plan.axis_sizes)


def distributed_metrics(plan: "agg.WirePlan", *, loss: jax.Array,
                        survivors: jax.Array,
                        fleet: Optional[Dict[str, jax.Array]] = None
                        ) -> Dict[str, Any]:
    """Assemble the distributed round's metrics dict (inside shard_map)."""
    m: Dict[str, Any] = {
        "loss": loss,
        "survivors": survivors,
        "wire_bits_per_param": jnp.float32(plan.wire_bits),
        "wire_phase_bits_per_param": {
            k: jnp.float32(v) for k, v in wire_phase_split(plan).items()},
    }
    if fleet is not None:
        m.update(fleet)
    return m


FLEET_METRIC_KEYS = ("cohort_energy_j", "energy_budget_j", "selected_valid",
                     "battery_total_j", "battery_q10_j", "battery_q50_j",
                     "battery_q90_j", "power_q10_w", "power_q50_w",
                     "power_q90_w", "outage_rate", "outage_target",
                     "harvested_j")


def distributed_metrics_structure(plan: "agg.WirePlan",
                                  with_fleet: bool) -> Dict[str, Any]:
    """A host-side template with the exact key structure
    :func:`distributed_metrics` emits — what ``make_fl_round`` maps to
    PartitionSpecs for the shard_map out_specs."""
    m: Dict[str, Any] = {
        "loss": 0.0, "survivors": 0.0, "wire_bits_per_param": 0.0,
        "wire_phase_bits_per_param": {k: 0.0
                                      for k in wire_phase_split(plan)},
    }
    if with_fleet:
        m.update({k: 0.0 for k in FLEET_METRIC_KEYS})
    return m


def fleet_round_metrics(*, battery_j: jax.Array, valid: jax.Array,
                        charge_j: jax.Array, power_w: jax.Array,
                        outage_sel: jax.Array, cost_sel: jax.Array,
                        harvest_j: jax.Array,
                        error_prob: float) -> Dict[str, jax.Array]:
    """The fleet extras of one round (scalars; shared by both runtimes).

    Power-policy accounting rides here: assigned-power quantiles over the
    whole fleet (``power_w`` = the policy's (N,) vector), the round's
    energy BUDGET (Σ assigned cohort cost) next to the REALIZED debit
    (``cohort_energy_j`` — lower when batteries clip at empty), the
    realized cohort outage rate (``outage_sel`` — the deadline-miss mask
    ``fleet.round_update`` decided, the same one the drop realization
    uses) against the configured FBL target, and the realized harvesting
    credit.
    """
    q = jnp.percentile(battery_j, jnp.asarray(BATTERY_QUANTILES))
    pq = jnp.percentile(power_w, jnp.asarray(BATTERY_QUANTILES))
    n_valid = jnp.sum(valid)
    outage = jnp.sum(outage_sel) / jnp.maximum(n_valid, 1.0)
    return {
        "cohort_energy_j": jnp.sum(charge_j),
        "energy_budget_j": jnp.sum(valid * cost_sel),
        "selected_valid": n_valid,
        "battery_total_j": jnp.sum(battery_j),
        "battery_q10_j": q[0], "battery_q50_j": q[1], "battery_q90_j": q[2],
        "power_q10_w": pq[0], "power_q50_w": pq[1], "power_q90_w": pq[2],
        "outage_rate": outage,
        "outage_target": jnp.float32(error_prob),
        "harvested_j": harvest_j,
    }


def simulator_round_telemetry(*, loss: jax.Array, accuracy: jax.Array,
                              selected: jax.Array, valid: jax.Array,
                              lam: jax.Array, battery_j: jax.Array,
                              charge_j: jax.Array, tau_s: jax.Array,
                              power_w: jax.Array, outage_sel: jax.Array,
                              cost_sel: jax.Array, harvest_j: jax.Array,
                              error_prob: float) -> Dict[str, jax.Array]:
    """One round of fleet-simulator telemetry (stacked by the scan)."""
    tel = {
        "loss": loss, "accuracy": accuracy,
        "selected": selected,                 # (K,) device ids
        "valid": valid,                       # (K,) filled-slot mask
        "survivors": jnp.sum(lam),
        "drops": jnp.sum(valid) - jnp.sum(lam),   # realized drops
        "tau_s": tau_s,
    }
    tel.update(fleet_round_metrics(battery_j=battery_j, valid=valid,
                                   charge_j=charge_j, power_w=power_w,
                                   outage_sel=outage_sel, cost_sel=cost_sel,
                                   harvest_j=harvest_j,
                                   error_prob=error_prob))
    return tel


#: stacked-telemetry keys expanded to python floats in the history dicts
_SCALAR_KEYS = ("loss", "survivors", "drops", "tau_s", "cohort_energy_j",
                "energy_budget_j", "selected_valid", "battery_total_j",
                "battery_q10_j", "battery_q50_j", "battery_q90_j",
                "power_q10_w", "power_q50_w", "power_q90_w", "outage_rate",
                "outage_target", "harvested_j")


def expand_history(stacked: Dict[str, jax.Array], rounds: int,
                   start_round: int = 0) -> List[Dict[str, Any]]:
    """Stacked scan telemetry -> the per-round history dicts of ``train``.

    Keeps the legacy keys (round/loss/accuracy/survivors/energy_j/tau_s)
    — ``energy_j`` is now the round's REALIZED cohort energy (the battery
    debit), not the static expected value — and adds the fleet extras.
    ``accuracy`` is the ONE canonical metric key: the scan body overwrites
    it in place when an ``eval_fn`` is folded in (no shadow ``metric``
    alias), so streamed tap records and this expansion read the same key.
    Each key is one blocking device -> host read (``sim/sync``).
    """
    host = {}
    for k, v in stacked.items():
        with obs_trace.host_span("sim/sync"):
            host[k] = np.asarray(v)
    history = []
    for t in range(rounds):
        h: Dict[str, Any] = {"round": start_round + t,
                             "accuracy": float(host["accuracy"][t]),
                             "energy_j": float(host["cohort_energy_j"][t])}
        for k in _SCALAR_KEYS:
            h[k] = float(host[k][t])
        h["survivors"] = int(h["survivors"])
        h["selected"] = host["selected"][t][
            host["valid"][t] > 0].astype(int).tolist()
        history.append(h)
    return history
