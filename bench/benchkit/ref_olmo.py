"""Plain reference of one FL round of an OLMo-style decoder.

Written from the published architecture (arXiv:2402.00838: non-parametric
LayerNorm, rotary positions, causal multi-head attention, SwiGLU MLP, tied
embeddings) and the paper's round (Algorithm 1: I local SGD steps per
cohort, n-bit stochastic quantization of the delta, Bernoulli packet
drops, error-aware aggregation).  It imports nothing of the program.

Arithmetic is float32 with every matrix product at ``highest`` precision.
The parameters, and so their gradients, are held in the dtype the
configuration states: each SGD step rounds ``w - lr * g`` to that dtype.
``dtype`` and ``dot`` are where a lower precision may enter: the control
that the comparison has to reject runs the whole round in bfloat16
(weights, activations and products: :func:`bf16_dot`).

The round draws its randomness exactly as the system's round is specified
to: per cohort ``c`` the stream ``fold_in(step_key, c)``, the packet draw
``uniform(fold_in(stream, 11)) >= q``, the quantizer's noise from
``split(fold_in(stream, 13), leaves)``, one key per parameter leaf in
tree order.

It is the OLMo architecture module of ``fl_round.ARCH_API``: with the
reference it provides the sizes it reads (:func:`dims`) and the
yardstick's counts, ``work.olmo_param_count`` and ``work.olmo_round_flops``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.work import olmo_param_count as param_count  # noqa: F401
from benchkit.work import olmo_round_flops as round_flops  # noqa: F401

PyTree = Any
HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-12


def dims(cfg) -> Dict[str, Any]:
    """The sizes of the configuration that the reference and the counts
    read."""
    m = cfg.model
    return {"d_model": m.d_model, "n_heads": m.n_heads,
            "n_kv_heads": m.n_kv_heads, "d_ff": m.d_ff,
            "vocab_size": m.vocab_size, "n_layers": m.n_layers,
            "rope_theta": m.rope_theta}


def f32_dot(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def bf16_dot(spec: str, a, b):
    """Products of bfloat16 inputs, rounded to bfloat16 (the control)."""
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _layernorm(x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta: float):
    """Rotary positions on (B, S, H, hd): rotate the two halves."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(p, x, model: Dict, dot: Callable):
    B, S, d = x.shape
    H = model["n_heads"]
    hd = d // H
    h = _layernorm(x)
    q = dot("bsd,de->bse", h, p["attn"]["wq"]).reshape(B, S, H, hd)
    k = dot("bsd,de->bse", h, p["attn"]["wk"]).reshape(B, S, H, hd)
    v = dot("bsd,de->bse", h, p["attn"]["wv"]).reshape(B, S, H, hd)
    q = _rope(q, model["rope_theta"]) * hd ** -0.5
    k = _rope(k, model["rope_theta"])
    s = dot("bqhe,bkhe->bhqk", q, k)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("bhqk,bkhe->bqhe", a, v).reshape(B, S, d)
    x = x + dot("bsd,de->bse", o, p["attn"]["wo"])
    h = _layernorm(x)
    up = dot("bsd,df->bsf", h, p["mlp"]["w_up"])
    gate = dot("bsd,df->bsf", h, p["mlp"]["w_gate"])
    ff = dot("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp"]["w_down"])
    return x + ff


def loss(params: PyTree, tokens, labels, model: Dict, dot: Callable,
         dtype=jnp.float32):
    """Mean next-token cross-entropy; layers run one at a time under
    ``lax.scan`` with their activations recomputed in the backward pass,
    so a full-size model fits beside its weights."""
    emb = params["embed"].astype(dtype)
    x = emb[tokens]

    @jax.checkpoint
    def body(x, p):
        p = jax.tree_util.tree_map(lambda w: w.astype(dtype), p)
        return _block(p, x, model, dot).astype(dtype), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _layernorm(x)
    logits = dot("bsd,vd->bsv", x, emb).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def make_sgd_step(model: Dict, lr: float, dot: Callable = f32_dot,
                  dtype=jnp.float32):
    """(params, tokens, labels) -> (params, loss): one local step.  The
    backward pass runs in ``dtype``; the gradient lands in the parameters'
    dtype and the update is rounded once to it."""
    def step(params, tokens, labels):
        l, g = jax.value_and_grad(loss)(params, tokens, labels, model, dot,
                                        dtype)
        new = jax.tree_util.tree_map(
            lambda w, gw: (w.astype(jnp.float32) - lr * gw).astype(w.dtype),
            params, g)
        return new, l
    return jax.jit(step, donate_argnums=(0,))


def _codes(x, key, bits: int, clip: float):
    """Stochastic rounding of x/clip to n-bit signed codes (int8 holds
    them for n <= 8)."""
    g = 2.0 ** (bits - 1)
    u = jax.random.uniform(key, x.shape, jnp.float32)
    c = jnp.floor(jnp.clip(x, -clip, clip) * (g / clip) + u)
    return jnp.clip(c, -g, g - 1).astype(jnp.int8 if bits <= 8 else
                                         jnp.int32)


def run_rounds(start: PyTree, rounds: List[Tuple[Dict, jax.Array]],
               model: Dict, fl: Dict, dot: Callable = f32_dot,
               dtype=jnp.float32, fault: str = ""):
    """Run FL rounds from ``start``; ``rounds`` holds each round's global
    batch (cohort c owns rows [c·B/K, (c+1)·B/K)) and step key.  Returns
    (params, [loss per round], [survivors per round]); ``start`` is
    consumed.  To fit beside a full-size model, the round's start
    parameters wait in host memory while one cohort at a time trains on
    the device, and its codes join an int32 running sum leaf by leaf.

    ``fault`` plants a known fault, to read how the comparison sees it:
    ``half_batch`` trains each step on the first half of its tokens only,
    ``no_exchange`` leaves out the sum across cohorts (cohort 0 keeps its
    own delta, loss and packet draw)."""
    K, I = fl["cohorts"], fl["local_iters"]
    bits, clip, q, lr = fl["bits"], fl["clip"], fl["error_prob"], fl["lr"]
    gain = 2.0 ** (bits - 1) / clip
    sgd = make_sgd_step(model, lr, dot, dtype)
    leaf_codes = jax.jit(
        lambda a, b, lam, key: _codes(
            (a.astype(jnp.float32) - b.astype(jnp.float32)) * lam, key,
            bits, clip))
    add = jax.jit(lambda acc, c: acc + c.astype(jnp.int32),
                  donate_argnums=(0,))
    apply = jax.jit(lambda w, t, den: w + (t.astype(jnp.float32) / gain
                                           / den).astype(w.dtype))

    params = start
    losses, survivors = [], []
    for batch, step_key in rounds:
        host = jax.device_get(params)
        del params
        leaves, treedef = jax.tree_util.tree_flatten(host)
        B = batch["tokens"].shape[0]
        b = B // K // I
        total, lam_sum, round_loss = None, 0.0, 0.0
        for c in range(K):
            stream = jax.random.fold_in(step_key, c)
            lam = float(jax.random.uniform(jax.random.fold_in(stream, 11),
                                           ()) >= q)
            local = jax.device_put(host)
            rows = slice(c * (B // K), (c + 1) * (B // K))
            cohort_loss = 0.0
            for i in range(I):
                mb = slice(rows.start + i * b, rows.start + (i + 1) * b)
                toks, labs = batch["tokens"][mb], batch["labels"][mb]
                if fault == "half_batch":
                    half = toks.shape[1] // 2
                    toks, labs = toks[:, :half], labs[:, :half]
                local, l = sgd(local, toks, labs)
                cohort_loss += float(l)
            local = jax.tree_util.tree_leaves(local)
            keys = jax.random.split(jax.random.fold_in(stream, 13),
                                    len(leaves))
            codes = []
            for j in range(len(leaves)):
                codes.append(leaf_codes(local[j], jax.device_put(leaves[j]),
                                        jnp.float32(lam), keys[j]))
                local[j] = None
            total = ([cd.astype(jnp.int32) for cd in codes] if total is None
                     else [add(t, cd) for t, cd in zip(total, codes)])
            del local, codes
            lam_sum += lam
            round_loss += cohort_loss / I
            if fault == "no_exchange":
                break
        n = 1 if fault == "no_exchange" else K
        den = jnp.float32(max(lam_sum / K, EPS) * K)
        params = jax.tree_util.tree_unflatten(treedef, [
            apply(jax.device_put(w), t, den) for w, t in zip(leaves, total)])
        del total
        losses.append(round_loss / n)
        survivors.append(lam_sum)
    return params, losses, survivors
