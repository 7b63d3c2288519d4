"""The sharding entry points every mesh/shard_map call site goes through
(floor: jax 0.9).

  make_mesh(shape, axes)      -> ``jax.make_mesh`` with every axis Auto
  set_mesh(mesh)              -> ``jax.set_mesh`` (the ambient mesh)
  shard_map(f, mesh, ...)     -> ``jax.shard_map``; ``axis_names`` is the
                                 set of Manual axes, the rest stay Auto
                                 (partial-auto: the ``model`` axis keeps
                                 tensor parallelism inside the FL round)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Set

import jax

set_mesh = jax.set_mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD)."""
    kwargs: Dict[str, Any] = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), **kwargs)


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool = True,
              axis_names: Optional[Set[str]] = None) -> Callable:
    """``jax.shard_map``; ``axis_names`` (default: every mesh axis) are
    Manual inside ``f`` and the remaining axes stay Auto (GSPMD)."""
    kwargs: Dict[str, Any] = {"mesh": mesh, "in_specs": in_specs,
                              "out_specs": out_specs, "check_vma": check_vma}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)

