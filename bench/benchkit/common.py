"""What every driver shares: the cell, the clock, compile counting, the
device description and the comparison of readings with their limits."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

#: jax.monitoring events that mean XLA compiled, or loaded a compiled
#: program from the persistent cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_json: Optional[Dict] = None) -> Cell:
    """Find a cell by name: its configuration file (``file`` of the
    configuration entry), its traffic mix ``bench/traffic/<traffic>.json``
    and its limits ``bench/limits/<cell>.json``."""
    spec = bench_json or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(os.path.join(ROOT, configs[w["config"]]["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits", name + ".json")))


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads from the moment it
    is armed; a measured window must see none."""

    def __init__(self):
        import jax
        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if self.armed and event == _COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if self.armed and event == _CACHE_HIT_EVENT:
            self.cache_loads += 1


def model_value(cfg, key: str) -> Any:
    """The program's model configuration at ``key``; a dotted key reads a
    nested group (``moe.num_experts``)."""
    value = cfg.model
    for part in key.split("."):
        value = getattr(value, part)
    return value


def check_model(cfg, sizes: Dict[str, Any]) -> None:
    """The program's model configuration must be what the file states."""
    wrong = {k: (model_value(cfg, k), v) for k, v in sizes.items()
             if model_value(cfg, k) != v}
    if wrong:
        raise ValueError(f"configuration file and program differ "
                         f"(program, file): {wrong}")


def device_info(devices) -> Dict[str, Any]:
    """The device as JAX reports it, with the peak bytes in use on the
    fullest chip (as the runtime counts them)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


# ---------------------------------------------------------------------------
# readings against limits
# ---------------------------------------------------------------------------

def rel_gap(a: float, b: float) -> float:
    """|a - b| / |b| (0 when both are 0, inf when only b is)."""
    if a == b:
        return 0.0
    return abs(a - b) / abs(b) if b else math.inf


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf of |‖Δ‖_prog - ‖Δ‖_ref| over the larger of the leaf's
    reference norm and the median leaf's: a leaf that barely moves is
    held to the scale of the others."""
    med = statistics.median(ref.values())
    worst = 0.0
    for name, r in ref.items():
        den = max(r, med)
        gap = abs(prog[name] - r)
        worst = max(worst, gap / den if den > 0 else
                    (0.0 if gap == 0 else math.inf))
    return worst


def judge(readings: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """{name: {value, limit}} for every limited reading; a reading that
    is missing or not a number fails."""
    out = {}
    for name, limit in limits.items():
        v = readings.get(name, math.nan)
        out[name] = {"value": float(v), "limit": float(limit)}
    return out


def all_within(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def now() -> float:
    return time.perf_counter()
