"""Operations and least bytes of each measured layer, from shapes alone.

These counts are the yardstick of the MFU and roofline metrics.  They
depend on the configuration and the traffic, never on how the program
implements a layer, so a faster implementation cannot move them.
"""
from __future__ import annotations

import math
from typing import Dict

#: FLOPs per multiply-accumulate
FLOP_PER_MAC = 2
#: forward + backward = 3 forward passes (no recomputation counted)
TRAIN_PASSES = 3


# ---------------------------------------------------------------------------
# OLMo (dense decoder, tied embeddings, gated MLP)
# ---------------------------------------------------------------------------

def olmo_param_count(model: Dict) -> int:
    """Every parameter of the decoder: tied embedding plus L blocks of
    attention (q, k, v, o) and a gated MLP (up, gate, down).  The
    non-parametric LayerNorms hold none."""
    d, ff, v, L = (model["d_model"], model["d_ff"], model["vocab_size"],
                   model["n_layers"])
    hd = d // model["n_heads"]
    attn = d * model["n_heads"] * hd * 2 + d * model["n_kv_heads"] * hd * 2
    mlp = 3 * d * ff
    return v * d + L * (attn + mlp)


def olmo_round_flops(model: Dict, tokens: int, seq_len: int) -> float:
    """Model FLOPs of one FL round's local steps over ``tokens`` tokens:
    6·N·T for the weight matmuls (forward and backward) plus 12·L·d·S·T
    for attention scores and values at sequence length S (the PaLM
    accounting, full S×S, no recomputation counted)."""
    n = olmo_param_count(model)
    attn = 12 * model["n_layers"] * model["d_model"] * seq_len
    return float(6 * n * tokens + attn * tokens)


# ---------------------------------------------------------------------------
# the paper's QNN (2 conv + 2 FC on 28x28x1)
# ---------------------------------------------------------------------------

def qnn_weights() -> int:
    conv1 = 3 * 3 * 1 * 32 + 32
    conv2 = 3 * 3 * 32 * 64 + 64
    fc1 = 7 * 7 * 64 * 128 + 128
    fc2 = 128 * 10 + 10
    return conv1 + conv2 + fc1 + fc2


def qnn_macs_per_sample() -> int:
    conv1 = 28 * 28 * 32 * (3 * 3 * 1)
    conv2 = 14 * 14 * 64 * (3 * 3 * 32)
    fc1 = 7 * 7 * 64 * 128
    fc2 = 128 * 10
    return conv1 + conv2 + fc1 + fc2


def qnn_round_flops(clients: int, local_iters: int, batch: int) -> float:
    """QNN training FLOPs of one round: every client's I local steps."""
    samples = clients * local_iters * batch
    return float(FLOP_PER_MAC * TRAIN_PASSES * qnn_macs_per_sample()
                 * samples)


# ---------------------------------------------------------------------------
# the quantized wire (aggregation interface)
# ---------------------------------------------------------------------------

def _lane(bits: int, summands: int) -> int:
    return bits + (math.ceil(math.log2(summands)) if summands > 1 else 0)


def wire_bits_per_param(mode: str, bits: int, cohorts: int) -> float:
    """Bits per parameter each chip puts on the wire for one aggregation
    over ``cohorts`` cohorts on one mesh axis: ``packed`` ships uint32
    words of carry-free lanes n + ceil(log2 K); ``int`` the smallest int
    container of the K-fold sum; ``ring`` K-1 hops at the native lane;
    ``rsag`` growing-lane scatter hops plus final-lane gather hops."""
    if mode == "packed":
        return 32.0 / (32 // _lane(bits, cohorts))
    if mode == "int":
        need = bits + math.ceil(math.log2(max(cohorts, 2)))
        return 8.0 if need <= 7 else 16.0 if need <= 15 else 32.0
    if mode == "ring":
        return (cohorts - 1) * 32.0 / (32 // bits)
    if mode == "rsag":
        rs = sum(32.0 / (32 // _lane(bits, h)) / cohorts
                 for h in range(1, cohorts))
        ag = (cohorts - 1) * 32.0 / (32 // _lane(bits, cohorts)) / cohorts
        return rs + ag
    raise ValueError(f"no wire count for mode {mode!r}")


def wire_interface_bytes(num_params: int, param_bytes: int,
                         wire_bits: float, cohorts: int) -> Dict[str, float]:
    """Least bytes of one aggregation, counted at its interface: read the
    start and the local params, write the new params, write and read the
    wire payload once; across chips, what a ring all-reduce of that
    payload must send from each chip, 2(K-1)/K of it."""
    payload = num_params * wire_bits / 8.0
    hbm = 3.0 * num_params * param_bytes + 2.0 * payload
    ici = 2.0 * (cohorts - 1) / cohorts * payload if cohorts > 1 else 0.0
    return {"hbm": hbm, "ici": ici}


def least_seconds(hbm_bytes: float, ici_bytes: float, flops: float,
                  peaks) -> float:
    """The roofline floor: the largest of the HBM, ICI and compute bounds."""
    return max(hbm_bytes / peaks.hbm_bytes_s, ici_bytes / peaks.ici_bytes_s,
               flops / peaks.bf16_flops)


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

#: (N,) float32 vectors of the fleet state: fading (re, im), pathloss,
#: battery, capacity, harvest scale, last power, availability
FLEET_VECTORS = 8


def fleet_state_bytes(devices: int) -> float:
    """Read and write the whole fleet state once (plus its int32 cursor)."""
    return 2.0 * (FLEET_VECTORS * 4 * devices + 4)
