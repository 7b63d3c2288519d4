"""Inputs and weights, made on the device from ``--seed``.

The token stream and the digit store are copies of the program's own
synthetic generators (``repro.data.synthetic``), kept here so that the
traffic a cell sends cannot change under a later PR; ``bench/tests/
test_data.py`` holds each copy to its original's draws, so the two cannot
drift apart unseen.  Weights are the
benchmark's: one jitted call draws every leaf of the program's parameter
tree from the seed, in the dtype the configuration states.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 2**63: the high bits fold in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def leaf_std(path: str, shape) -> float:
    """Embeddings N(0, 0.02); weights N(0, 1/fan_in); vectors zero.  The
    fan-in of a layer stack (under ``blocks``, layers on the leading axis)
    is the second-to-last axis, of any other weight every axis but the
    last (a convolution's kernel window times its input channels)."""
    if "embed" in path:
        return 0.02
    if len(shape) < 2:
        return 0.0
    fan_in = shape[-2] if "blocks" in path else int(np.prod(shape[:-1]))
    return float(fan_in) ** -0.5


def make_weights(key: jax.Array, shapes: PyTree) -> PyTree:
    """Every leaf of ``shapes`` (a tree of ShapeDtypeStruct) drawn from
    ``key``; leaf ``i`` uses ``fold_in(key, i)``.  Call under ``jax.jit``
    with the program's out_shardings to build the weights in one call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        std = leaf_std(jax.tree_util.keystr(path), s.shape)
        w = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                              jnp.float32) * std
        out.append(w.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# token traffic (copy of repro.data.synthetic.token_batch)
# ---------------------------------------------------------------------------

def token_batch(key, batch: int, seq_len: int, vocab: int
                ) -> Dict[str, jax.Array]:
    """Markov-ish token stream: each token is a fresh draw or the
    deterministic successor of one; labels are the next token."""
    k1, k2 = jax.random.split(key)
    base = jax.random.randint(k1, (batch, seq_len), 0, vocab)
    shifted = (base * 31 + 7) % vocab
    mix = jax.random.bernoulli(k2, 0.5, (batch, seq_len))
    tokens = jnp.where(mix, base, shifted).astype(jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    return {"tokens": tokens, "labels": labels}


def round_inputs(key, batch: int, seq_len: int, vocab: int):
    """The trainer's per-round draw: (next key, batch, step key)."""
    key, k_data, k_step = jax.random.split(key, 3)
    return key, token_batch(k_data, batch, seq_len, vocab), k_step


# ---------------------------------------------------------------------------
# digit store (copy of repro.data.synthetic.digit_dataset / partition_iid)
# ---------------------------------------------------------------------------

def digit_templates(key, num_classes: int = 10, size: int = 28):
    raw = jax.random.normal(key, (num_classes, size, size))
    t = raw
    for _ in range(2):
        t = sum(jnp.roll(jnp.roll(t, i, 1), j, 2)
                for i in (-1, 0, 1) for j in (-1, 0, 1)) / 9.0
    t = t - t.mean(axis=(1, 2), keepdims=True)
    return t / (t.std(axis=(1, 2), keepdims=True) + 1e-6)


def digit_dataset(key, num_samples: int, num_classes: int = 10,
                  size: int = 28, noise: float = 0.6):
    """{"images": (N, 28, 28, 1) f32, "labels": (N,) int32}."""
    k_t, k_y, k_n, k_s = jax.random.split(key, 4)
    templates = digit_templates(k_t, num_classes, size)
    labels = jax.random.randint(k_y, (num_samples,), 0, num_classes)
    imgs = templates[labels]
    shifts = jax.random.randint(k_s, (num_samples, 2), -2, 3)
    imgs = jax.vmap(lambda im, s: jnp.roll(im, s, axis=(0, 1)))(imgs, shifts)
    imgs = imgs + noise * jax.random.normal(k_n, imgs.shape)
    return {"images": imgs[..., None].astype(jnp.float32),
            "labels": labels.astype(jnp.int32)}


def partition_iid(key, num_samples: int, num_clients: int) -> List[np.ndarray]:
    perm = np.asarray(jax.random.permutation(key, num_samples))
    return [np.sort(s) for s in np.array_split(perm, num_clients)]


def federated_digits(key, num_samples: int, num_clients: int):
    """(data, partitions) of an IID digit store."""
    k_data, k_part = jax.random.split(key)
    data = jax.jit(digit_dataset, static_argnums=(1,))(k_data, num_samples)
    return data, partition_iid(k_part, num_samples, num_clients)
