"""Activation-sharding hooks usable from pure model code.

Models call ``shard(x, "batch", None, "tensor")`` with *logical* axis names;
if a mesh context is active (``use_sharding_rules``), the logical names are
resolved to physical mesh axes (divisibility-checked) and a
``with_sharding_constraint`` is applied; otherwise it's a no-op — so the same
model code runs on 1 CPU device and on a 512-chip mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical name -> tuple of physical mesh axes to try (in order)
DEFAULT_RULES = {
    "batch": (("pod", "data"), ("data",)),
    "seq": (("data",),),          # sequence-parallel decode (long_500k)
    "tensor": (("model",),),
    "expert": (("model",),),
    "fsdp": (("data",),),
    "vocab": (("model",),),
}


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def resolve_logical(mesh: Mesh, logical: Optional[str], dim_size: int):
    """Logical axis -> physical axes (or None), honoring divisibility."""
    if logical is None:
        return None
    for candidate in current_rules().get(logical, ()):
        phys = tuple(a for a in candidate if a in mesh.shape)
        if not phys:
            continue
        if dim_size % _axes_size(mesh, phys) == 0:
            return phys if len(phys) > 1 else phys[0]
    return None  # replicate


@contextlib.contextmanager
def use_sharding_rules(mesh: Optional[Mesh], overrides: Optional[dict] = None):
    """Activate logical->physical rules. ``overrides`` patches DEFAULT_RULES,
    e.g. {"batch": ((("pod","data","model"),), ...)} for dp-over-model."""
    prev = getattr(_state, "mesh", None)
    prev_rules = getattr(_state, "rules", None)
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES, **(overrides or {}))
    try:
        yield
    finally:
        _state.mesh = prev
        _state.rules = prev_rules


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_rules() -> dict:
    return getattr(_state, "rules", None) or DEFAULT_RULES


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the resolved sharding of the active mesh (no-op otherwise)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} array")
    # axes Manual in the current trace (inside a shard_map body) may not
    # appear in a sharding constraint
    manual = jax.sharding.get_abstract_mesh().manual_axes

    def resolve(name, size):
        phys = resolve_logical(mesh, name, size)
        if phys is None:
            return None
        axs = phys if isinstance(phys, tuple) else (phys,)
        axs = tuple(a for a in axs if a not in manual)
        if not axs:
            return None
        return axs if len(axs) > 1 else axs[0]

    spec = P(*[resolve(name, x.shape[i])
               for i, name in enumerate(logical_axes)])
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
