"""Fused Adam update as a single-pass pallas kernel.

The TPU analog of the reference's fused CUDA adam kernel
(/root/reference/paddle/fluid/operators/optimizers/adam_op.h AdamFunctor:
one pass over param/grad/moments). The XLA lowering of the same update
(ops/optimizer_ops.py) runs at ~40% of HBM bandwidth on the profiled GPT
step because the convert/subtract chains split into several fusions; this
kernel does the whole update — bf16 grad in, fp32 moments, bias-corrected
step, bf16/fp32 param out — in one read and one write per buffer, with
the param/moment buffers aliased in place.

Used automatically by the `adam`/`adamw` lowerings for tile-aligned
parameters on TPU; odd shapes fall back to the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
            *, beta1, beta2, eps, weight_decay):
    lr = sc_ref[0]
    b1p = sc_ref[1]
    b2p = sc_ref[2]
    g = g_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    denom = jnp.sqrt(v) / jnp.sqrt(1.0 - b2p) + eps
    p = p_ref[:].astype(jnp.float32)
    step = lr * (m / denom) / (1.0 - b1p)
    if weight_decay:
        step = step + lr * weight_decay * p
    po_ref[:] = (p - step).astype(po_ref.dtype)
    mo_ref[:] = m
    vo_ref[:] = v


def _block(rows, cols):
    """Pick a (BR, BC) VMEM block under ~2MB of fp32 working set; BR must
    divide rows and stay a multiple of 8 (TPU sublane tile)."""
    bc = cols if cols <= 1024 else 512
    # 7 live buffers x double buffering: keep each block ~<=0.5MB fp32
    limit = max(8, (1 << 19) // (bc * 4))
    br = min(rows, limit - limit % 8)
    while br > 8 and rows % br:
        br -= 8
    return br, bc


def supported(p, g, m, v) -> bool:
    """2-D tile-aligned params only; the long tail (biases, layernorm
    gains) carries negligible traffic and keeps the jnp path."""
    if p.ndim != 2:
        return False
    r, c = p.shape
    if r % 8 or c % 128:
        return False
    return g.shape == p.shape and m.shape == p.shape and v.shape == p.shape


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "eps", "weight_decay", "interpret"))
def fused_adam(p, g, m, v, lr, beta1_pow, beta2_pow,
               *, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0,
               interpret=False):
    """One fused in-place Adam step. p: bf16/fp32 [R,C]; m,v: fp32 [R,C].
    Returns (p_out, m_out, v_out) aliased onto the inputs."""
    rows, cols = p.shape
    br, bc = _block(rows, cols)
    grid = (rows // br, pl.cdiv(cols, bc))
    m = m.astype(jnp.float32)
    v = v.astype(jnp.float32)
    scalars = jnp.stack(
        [jnp.asarray(lr, jnp.float32).reshape(()),
         jnp.asarray(beta1_pow, jnp.float32).reshape(()),
         jnp.asarray(beta2_pow, jnp.float32).reshape(())]
    )
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(
            _kernel, beta1=float(beta1), beta2=float(beta2),
            eps=float(eps), weight_decay=float(weight_decay),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec, spec, spec, spec,
        ],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="fused_adam",
    )(scalars, p, g, m, v)
