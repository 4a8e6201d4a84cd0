"""Seed-made serving weights, generated ON the device in one jitted call.

``serving.model.init_params`` draws every normal with numpy on the host
(1.5 G draws and a 3 GB upload for GPT-2 XL, in every process); the
benchmark instead builds the same name -> array dict (the names of
models/gpt.py, GPT-2's initialisation: N(0, 0.02), residual projections
scaled by 1/sqrt(2L), LayerNorm at 1 / 0, biases 0) with ``jax.random`` in
the serving dtype and passes it as ``DecodeModel(params=...)``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple


def param_table(n_layer: int, d: int, dff: int, vocab: int, n_pos: int
                ) -> Dict[str, Tuple[tuple, float]]:
    """name -> (shape, std); std 0 means zeros, -1 means ones."""
    res = 0.02 / math.sqrt(2 * n_layer)
    t: Dict[str, Tuple[tuple, float]] = {
        "gpt.wte": ((vocab, d), 0.02), "gpt.wpe": ((n_pos, d), 0.02),
        "gpt.lnf.scale": ((d,), -1.0), "gpt.lnf.bias": ((d,), 0.0)}
    for i in range(n_layer):
        ln = f"gpt.h{i}"
        for part in ("q", "k", "v"):
            t[f"{ln}.attn.{part}.w"] = ((d, d), 0.02)
            t[f"{ln}.attn.{part}.b"] = ((d,), 0.0)
        t[f"{ln}.attn.proj.w"] = ((d, d), res)
        t[f"{ln}.attn.proj.b"] = ((d,), 0.0)
        t[f"{ln}.mlp.fc_in.w"] = ((d, dff), 0.02)
        t[f"{ln}.mlp.fc_in.b"] = ((dff,), 0.0)
        t[f"{ln}.mlp.fc_out.w"] = ((dff, d), res)
        t[f"{ln}.mlp.fc_out.b"] = ((d,), 0.0)
        for nrm in ("ln1", "ln2"):
            t[f"{ln}.{nrm}.scale"] = ((d,), -1.0)
            t[f"{ln}.{nrm}.bias"] = ((d,), 0.0)
    return t


def make_params(table: Dict[str, Tuple[tuple, float]], seed: int, dtype: str):
    """All arrays from one jitted call; the same seed gives the same weights."""
    import jax
    import jax.numpy as jnp

    names = sorted(table)

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, std = table[name]
            if std > 0:
                out[name] = (std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                                     jnp.float32)).astype(dtype)
            else:
                out[name] = jnp.full(shape, 1.0 if std < 0 else 0.0, dtype)
        return out

    return build(jax.random.key(int(seed)))
