"""Plain reference of the energy-aware fleet simulation (the paper's QNN).

Written from the paper (§II: stochastic n-bit quantization, QNN local
training with a straight-through estimator, finite-blocklength uplink,
Bernoulli packet errors, error-aware aggregation eq. 6, the energy model
eq. 7/9) and the fleet model the configuration states: Gauss-Markov AR(1)
Rayleigh fading per pathloss class, per-round availability, per-device
power by finite-blocklength rate inversion (``fbl_target``), uniform
random cohorts among the devices that are awake and can pay the round,
outage drops, battery debits.  It imports nothing of the program.

Randomness is drawn exactly as the simulation is specified to draw it
(the key tree is part of its semantics); the arithmetic is ``dtype``
(float32 at ``highest`` precision, or the bfloat16 control), with every
random draw made in float32 and then rounded to ``dtype``.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
HIGHEST = jax.lax.Precision.HIGHEST
FLEET_STREAM = 0xF1EE7


# ---------------------------------------------------------------------------
# physical layer and energy (paper §II-D)
# ---------------------------------------------------------------------------

def noise_w(ch: Dict) -> float:
    return 10.0 ** (ch["noise_psd_dbm"] / 10.0) * 1e-3


def fbl_rate(snr, ch: Dict, dt):
    """C(snr) - sqrt(V(snr)/M) Q^-1(q), clipped at 0 (Polyanskiy et al.)."""
    qinv = statistics.NormalDist().inv_cdf(1.0 - ch["error_prob"])
    cap = jnp.log2(1.0 + snr)
    disp = (1.0 - (1.0 + snr) ** -2) * (1.0 / math.log(2.0)) ** 2
    disp = jnp.maximum(disp, jnp.asarray(1e-12, dt))
    r = cap - jnp.sqrt(disp / ch["blocklength"]) * jnp.asarray(qinv, dt)
    return jnp.maximum(r, 0.0).astype(dt)


def required_snr(rate: float, ch: Dict, dt, iters: int = 60):
    """Least SNR whose FBL rate reaches ``rate``: bisection in log-SNR
    over [1e-9, 1e14]."""
    lo = jnp.asarray(math.log(1e-9), dt)
    hi = jnp.asarray(math.log(1e14), dt)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        ok = fbl_rate(jnp.exp(mid), ch, dt) >= rate
        lo, hi = jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)
    return jnp.exp(hi)


def min_rate(cfg: Dict, num_params: int) -> float:
    """Rate below which the d·n payload misses the round deadline."""
    return (num_params * cfg["quant"]["bits"]
            / (cfg["channel"]["bandwidth_hz"] * cfg["fl"]["tau_limit_s"]))


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

def init_fleet(key, cfg: Dict, dt) -> Dict:
    f, ch = cfg["fleet"], cfg["channel"]
    n = f["size"]
    k_cls, k_h, k_b = jax.random.split(key, 3)
    classes = jnp.asarray(f["pathloss_classes"], jnp.float32)
    pathloss = classes[jax.random.choice(k_cls, classes.shape[0], (n,))]
    std = jnp.sqrt(ch["rayleigh_scale"] * pathloss / 2.0)
    k1, k2 = jax.random.split(k_h)
    h_re = jax.random.normal(k1, (n,), jnp.float32) * std
    h_im = jax.random.normal(k2, (n,), jnp.float32) * std
    u = jax.random.uniform(k_b, (n,))
    battery = f["battery_j"] * (1.0 + f["battery_spread"] * (2.0 * u - 1.0))
    state = {"h_re": h_re, "h_im": h_im, "pathloss": pathloss,
             "battery_j": battery, "p_last": jnp.zeros((n,)),
             "available": jnp.ones((n,))}
    return {k: v.astype(dt) for k, v in state.items()}


def fleet_round(state: Dict, key, cfg: Dict, num_params: int, dt):
    """One round of the fleet: returns (state, idx, valid, lam)."""
    f, ch, fl, pw = cfg["fleet"], cfg["channel"], cfg["fl"], cfg["power"]
    n, K = f["size"], fl["devices_per_round"]
    k_ch, k_sel, k_drop = jax.random.split(key, 3)
    k_fade, k_avail = jax.random.split(k_ch)
    k1, k2 = jax.random.split(k_fade)
    rho = f["fading_rho"]
    std = jnp.sqrt(ch["rayleigh_scale"] * state["pathloss"] / 2.0)
    c = math.sqrt(max(1.0 - rho * rho, 0.0))
    w_re = (jax.random.normal(k1, (n,), jnp.float32).astype(dt) * std)
    w_im = (jax.random.normal(k2, (n,), jnp.float32).astype(dt) * std)
    h_re = rho * state["h_re"] + c * w_re
    h_im = rho * state["h_im"] + c * w_im
    available = (jax.random.uniform(k_avail, (n,))
                 < f["availability"]).astype(dt)
    gain2 = h_re * h_re + h_im * h_im

    # fbl_target power: the least power reaching the deadline rate
    r_min = min_rate(cfg, num_params)
    snr_req = required_snr(r_min * pw["fbl_rate_margin"], ch, dt)
    nw = noise_w(ch)
    p = jnp.clip(snr_req * nw / jnp.maximum(gain2, 1e-30),
                 pw["p_min"], pw["p_max"]).astype(dt)
    rates = fbl_rate(p * gain2 / nw, ch, dt)

    # round cost: local training (eq. 7) + deadline-capped uplink (eq. 9)
    e_cfg = cfg["energy"]
    payload = float(num_params * cfg["quant"]["bits"])
    e_l = (e_cfg["beta"] * e_cfg["cycles_per_bit"] * e_cfg["cpu_freq_hz"] ** 2
           * payload * fl["local_iters"])
    tau = payload / (ch["bandwidth_hz"] * jnp.maximum(rates, 1e-12))
    cost = (e_l + jnp.minimum(tau, fl["tau_limit_s"]) * p).astype(dt)

    # uniform cohort among the awake devices that can pay the round
    score = jax.random.uniform(k_sel, (n,))
    eligible = (available > 0) & (state["battery_j"] >= cost)
    top, idx = jax.lax.top_k(jnp.where(eligible, score, -jnp.inf), K)
    valid = jnp.isfinite(top).astype(dt)

    # drops: probability q, or 1 in outage (rate under the deadline rate)
    r_sel = rates[idx]
    p_err = jnp.where(r_sel > r_min, ch["error_prob"], 1.0)
    lam = valid * (jax.random.uniform(k_drop, (K,)) >= p_err).astype(dt)

    charge = jnp.minimum(state["battery_j"][idx], valid * cost[idx])
    battery = state["battery_j"].at[idx].add(-charge)
    new = dict(state, h_re=h_re, h_im=h_im, available=available,
               battery_j=battery, p_last=p)
    return new, idx, valid, lam


# ---------------------------------------------------------------------------
# the QNN and its local training (paper §IV, eq. 4)
# ---------------------------------------------------------------------------

def qnn_forward(p: Dict, images, dt):
    prec = HIGHEST if dt == jnp.float32 else None
    x = images.astype(dt)

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec)
        return y + b

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf,
                                     jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                                     "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1_w"], p["conv1_b"])))
    x = pool(jax.nn.relu(conv(x, p["conv2_w"], p["conv2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1_w"], precision=prec) + p["fc1_b"])
    return jnp.dot(x, p["fc2_w"], precision=prec) + p["fc2_b"]


def quantize(x, key, bits: int, clip: float, dt):
    """Stochastic n-bit quantize-dequantize of x/clip (paper §II-A/B)."""
    g = 2.0 ** (bits - 1)
    u = jax.random.uniform(key, x.shape, jnp.float32).astype(dt)
    c = jnp.floor(jnp.clip(x, -clip, clip) * (g / clip) + u)
    return (jnp.clip(c, -g, g - 1) / (g / clip)).astype(dt)


def _tree_quantize(tree: Dict, key, q: Dict, dt) -> Dict:
    names = sorted(tree)
    keys = jax.random.split(key, len(names))
    return {n: quantize(tree[n], k, q["bits"], q["clip"], dt)
            for n, k in zip(names, keys)}


def qat_loss(params: Dict, batch: Dict, key, q: Dict, dt):
    """Cross-entropy through straight-through fake-quantized weights: the
    forward sees the quantized weights, the gradient passes where
    |w| <= clip."""
    fq = _tree_quantize(params, key, q, dt)
    ste = {n: params[n] * (jnp.abs(params[n]) <= q["clip"])
           + jax.lax.stop_gradient(fq[n] - params[n]
                                   * (jnp.abs(params[n]) <= q["clip"]))
           for n in params}
    logits = qnn_forward(ste, batch["images"], dt).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)
    return -jnp.mean(ll)


def client_update(params: Dict, batches: Dict, key, cfg: Dict, dt,
                  fault: str = ""):
    """I local QAT-SGD steps, then the quantized delta.  ``fault`` =
    ``half_batch`` trains on the first half of each minibatch only."""
    fl, q = cfg["fl"], cfg["quant"]
    keys = jax.random.split(key, fl["local_iters"])
    p, losses = params, []
    for i in range(fl["local_iters"]):
        b = {k: v[i] for k, v in batches.items()}
        if fault == "half_batch":
            b = {k: v[: v.shape[0] // 2] for k, v in b.items()}
        l, g = jax.value_and_grad(qat_loss)(p, b, keys[i], q, dt)
        p = {n: (p[n] - fl["learning_rate"] * g[n]).astype(dt) for n in p}
        losses.append(l)
    delta = {n: p[n] - params[n] for n in p}
    delta = _tree_quantize(delta, jax.random.fold_in(key, 7), q, dt)
    return delta, jnp.mean(jnp.stack(losses))


def sim_round(params, state, k_round, batches, alphas, cfg, num_params, dt,
              fault: str = ""):
    """One simulated round: fleet, K client updates, eq.-6 aggregation."""
    K = cfg["fl"]["devices_per_round"]
    k_fleet, k_cli = jax.random.split(k_round)
    state, idx, valid, lam = fleet_round(state, k_fleet, cfg, num_params, dt)
    keys = jax.random.split(k_cli, K)
    deltas, losses = [], []
    for k in range(K):
        d, l = client_update(params, {n: v[k] for n, v in batches.items()},
                             keys[k], cfg, dt, fault)
        deltas.append(d)
        losses.append(l)
    w = alphas.astype(dt) * valid * lam
    den = jnp.maximum(jnp.sum(w), 1e-12)
    params = {n: (params[n] + sum(w[k] * deltas[k][n] for k in range(K))
                  / den).astype(dt) for n in params}
    return params, state, idx, valid, lam, jnp.mean(jnp.stack(losses))


def paper_round(params, k_run, batches, alphas, cfg, dt, fault: str = ""):
    """One round of the paper's homogeneous cohort (no fleet): K client
    updates, i.i.d. Bernoulli(1 - q) packet success, eq.-6 aggregation."""
    K = cfg["fl"]["devices_per_round"]
    keys = jax.random.split(k_run, K + 1)
    deltas, losses = [], []
    for k in range(K):
        d, l = client_update(params, {n: v[k] for n, v in batches.items()},
                             keys[k], cfg, dt, fault)
        deltas.append(d)
        losses.append(l)
    lam = (jax.random.uniform(keys[K], (K,))
           >= cfg["channel"]["error_prob"]).astype(dt)
    w = alphas.astype(dt) * lam
    den = jnp.maximum(jnp.sum(w), 1e-12)
    params = {n: (params[n] + sum(w[k] * deltas[k][n] for k in range(K))
                  / den).astype(dt) for n in params}
    return params, lam, jnp.mean(jnp.stack(losses))


# ---------------------------------------------------------------------------
# host-side input draws: cohort of data clients and their minibatches
# ---------------------------------------------------------------------------

def round_batches(k, data: Dict, partitions: List[np.ndarray], cfg: Dict):
    """Per round: K data clients without replacement, I minibatches each
    (with replacement only from a shard smaller than the batch)."""
    fl = cfg["fl"]
    B = cfg["train"]["global_batch"]
    k_sel, k_data, _ = jax.random.split(k, 3)
    clients = np.asarray(jax.random.choice(
        k_sel, len(partitions), (fl["devices_per_round"],), replace=False))
    out = {"images": [], "labels": []}
    for i, c in enumerate(clients):
        ks = jax.random.split(jax.random.fold_in(k_data, i),
                              fl["local_iters"])
        part = partitions[int(c)]
        rows = [jax.random.choice(kk, jnp.asarray(part), (B,),
                                  replace=len(part) < B) for kk in ks]
        for name in out:
            out[name].append(jnp.stack([data[name][r] for r in rows]))
    return {n: jnp.stack(v) for n, v in out.items()}, clients


def run_chunk(params: Dict, fleet_key, rng, rounds: int, data: Dict,
              partitions: List[np.ndarray], cfg: Dict, num_params: int,
              dt=jnp.float32, fault: str = ""):
    """``rounds`` rounds from the initial fleet drawn from ``fleet_key``,
    driven by the chunk key ``rng``: the inputs of round t come from the
    t-th split of ``rng``, the fleet and the clients from the key chain
    ``fold_in(rng, FLEET_STREAM)``.  Returns (params, fleet state, one
    record per round: loss, survivors, selected ids)."""
    sizes = np.array([len(p) for p in partitions], np.float64)
    alphas_all = jnp.asarray(sizes / sizes.sum(), jnp.float32)
    params = {n: v.astype(dt) for n, v in params.items()}
    if cfg["fleet"]["size"] == 0:
        return paper_chunk(params, rng, rounds, data, partitions, cfg,
                           alphas_all, dt, fault)
    state = init_fleet(fleet_key, cfg, dt)
    step = jax.jit(lambda p, s, k, b, a: sim_round(p, s, k, b, a, cfg,
                                                    num_params, dt, fault))
    carry = jax.random.fold_in(rng, FLEET_STREAM)
    records = []
    for _ in range(rounds):
        rng, k = jax.random.split(rng)
        batches, clients = round_batches(k, data, partitions, cfg)
        carry, k_round = jax.random.split(carry)
        params, state, idx, valid, lam, loss = step(
            params, state, k_round, batches, alphas_all[clients])
        records.append({"loss": float(loss), "survivors": float(jnp.sum(lam)),
                        "selected": [int(i) for i, v in zip(
                            np.asarray(idx), np.asarray(valid)) if v > 0]})
    return params, state, records


def paper_chunk(params, rng, rounds, data, partitions, cfg, alphas_all, dt,
                fault):
    """The homogeneous cohort's chunk: round t draws its inputs and its
    round key from the t-th split of ``rng``."""
    step = jax.jit(lambda p, k, b, a: paper_round(p, k, b, a, cfg, dt,
                                                  fault))
    records = []
    for _ in range(rounds):
        rng, k = jax.random.split(rng)
        batches, clients = round_batches(k, data, partitions, cfg)
        k_run = jax.random.split(k, 3)[2]
        params, lam, loss = step(params, k_run, batches,
                                 alphas_all[clients])
        records.append({"loss": float(loss), "survivors": float(jnp.sum(lam)),
                        "selected": []})
    return params, None, records
