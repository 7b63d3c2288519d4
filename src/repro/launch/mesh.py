"""Production meshes.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

from repro.utils import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds the 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_cohort_mesh(devices: int):
    """(devices, 1) over (data, model): one FL client cohort per device."""
    return compat.make_mesh((devices, 1), ("data", "model"))


def make_debug_mesh(devices: int = 8):
    """Small mesh for CPU tests: (devices//4, 4) over (data, model)."""
    assert devices % 4 == 0
    return compat.make_mesh((devices // 4, 4), ("data", "model"))
