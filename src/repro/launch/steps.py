"""Jit-able step builders shared by the dry-run, trainer and server.

``make_train_step``: the FL round when the config's cohort axes exist on the
mesh (the paper's technique — quantized deltas, Bernoulli drops, error-aware
renormalizing aggregation), else the standard data-parallel SGD step (the
FSDP fallback for archs whose full replica cannot live on one data shard).
Both have signature (params, batch, rng) -> (params, metrics).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import Config
from repro.core import fl as fl_mod

PyTree = Any


def make_standard_train_step(model, config: Config) -> Callable:
    """Plain SGD step (paper eq. 3 at cohort level); GSPMD all-reduces grads."""
    eta = config.fl.learning_rate

    def step(params, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch, rng)
        params = jax.tree_util.tree_map(
            lambda w, g: w - eta * g.astype(w.dtype), params, grads)
        return params, {"loss": loss}

    return step


def make_train_step(model, config: Config, mesh, *,
                    collective: Optional[str] = None,
                    force_standard: bool = False,
                    tap: Optional[Callable] = None) -> Tuple[Callable, str]:
    """Returns (step_fn, kind) with kind in {"fl_round", "fleet_fl_round",
    "standard"}.

    ``collective=None`` resolves ``config.quant.wire_format``.  When
    ``config.fleet.enabled`` the FL round threads a
    ``population.fleet.FleetState`` — signature (params, batch, rng,
    fleet) -> (params, metrics, fleet) — and kind is "fleet_fl_round".
    ``tap`` streams each round's metrics dict out of the shard_map while
    the step executes (see ``make_fl_round``; e.g.
    ``repro.obs.tap.step_sink_tap``); FL kinds only, ``None`` = off.
    A tapped FL step takes one extra trailing ``step`` int32 scalar that
    stamps each streamed record with its true round index."""
    if not force_standard:
        fl_round = fl_mod.make_fl_round(model, config, mesh,
                                        collective=collective, tap=tap)
        if fl_round is not None:
            kind = "fleet_fl_round" if config.fleet.enabled else "fl_round"
            return fl_round, kind
    return make_standard_train_step(model, config), "standard"


def make_prefill_step(model, config: Config) -> Callable:
    if config.model.is_encoder_decoder:
        return lambda params, tokens, frames: model.prefill(params, tokens, frames)
    return lambda params, tokens: model.prefill(params, tokens)


def make_decode_step(model, config: Config) -> Callable:
    return lambda params, cache, tokens: model.decode_step(params, cache, tokens)
