"""Operations and bytes an algorithm NEEDS, computed from shapes.

The yardstick's arithmetic: nothing here reads the program or the
compiler's cost analysis (XLA sees a pallas kernel only through the
kernel's own CostEstimate, and two of the three families declare none).
A matmul of (m, k) x (k, n) is 2*m*k*n operations; recomputation inside a
kernel (the flash backward re-forming the scores, the CE backward
re-forming the logits) is NOT counted: a roofline share is measured
against the work the mathematics requires.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """Peaks of one chip from benchmark/peaks.json; an unknown kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def n_params(cfg: dict) -> int:
    """Parameters of the GPT-2 block stack as the repo builds it (tied
    head, biases, learned positions)."""
    d, L, V, T = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    dff = cfg.get("n_inner") or 4 * d
    per_layer = (4 * d * d + 4 * d) + (2 * d * dff + dff + d) + 4 * d
    return V * d + T * d + L * per_layer + 2 * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6N + 12*L*T*d: forward + backward matmul operations per trained
    token (N counts the tied embedding once: it is the lm-head matmul),
    plus full (non-causal-discounted) attention scores and values. The
    PaLM / nanoGPT convention, copied from bench.py."""
    return 6.0 * n_params(cfg) + 12.0 * cfg["n_layer"] * seq * cfg["n_embd"]


def mfu(tokens_per_s: float, cfg: dict, seq: int, chips: int,
        peak_flops: float) -> float:
    return tokens_per_s * train_flops_per_token(cfg, seq) / (chips * peak_flops)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """Least time one chip could take, and which bound applies."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory",
            "compute_s": t_c, "memory_s": t_m}


def flash_attention_step(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """Causal attention forward + backward of ONE training step, all
    layers. Forward: S = Q K^T and O = P V. Backward: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q. Six matmuls of 2*T*T*hd per head,
    halved because the causal mask makes half of every product
    unnecessary. Bytes: q, k, v read and o written forward (4 tensors);
    q, k, v, o, do read and dq, dk, dv written backward (8 tensors)."""
    L, H = cfg["n_layer"], cfg["n_head"]
    hd = cfg["n_embd"] // H
    mm = 2.0 * seq * seq * hd
    flops = L * batch * H * 6 * mm * 0.5
    tensor = batch * seq * H * hd * itemsize
    nbytes = L * (4 * tensor + 8 * tensor)
    return {"flops": flops, "bytes": nbytes}


def lmhead_ce_step(cfg: dict, batch: int, seq: int, itemsize: int = 2) -> dict:
    """Tied lm-head + cross-entropy forward + backward of one step: the
    logits matmul forward, dX = dlogits W and dW = dlogits^T X backward:
    3 matmuls of 2*N*D*V. Bytes: X and W read per pass (3 passes), dX and
    dW written; the [N, V] logits are never required in HBM."""
    n, d, v = batch * seq, cfg["n_embd"], cfg["vocab_size"]
    flops = 3 * 2.0 * n * d * v
    nbytes = itemsize * (3 * (n * d + v * d) + n * d + v * d)
    return {"flops": flops, "bytes": nbytes}


def decode_tick_bytes(cfg: dict, live_kv_tokens: float, itemsize: int = 2) -> float:
    """Bytes one decode tick must stream: every weight once plus the K and
    V of every live context position (all layers)."""
    weights = n_params(cfg) * itemsize
    kv = 2.0 * cfg["n_layer"] * cfg["n_embd"] * itemsize * live_kv_tokens
    return weights + kv
