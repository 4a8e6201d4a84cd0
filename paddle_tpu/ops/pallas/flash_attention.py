"""Flash attention as pallas TPU kernels (forward + backward).

The flagship TPU-native kernel. No reference twin: goodcoder-cnn/Paddle's
`operators/fused/` has only inference-time multihead_matmul fusions; its
training attention materializes the full (T, T) probability tensor. Here
softmax(QK^T)V runs as a blocked online-softmax kernel that never leaves
VMEM for the score tile, with fp32 accumulators over bf16 inputs (MXU
native), a causal block-skip schedule, and a flash backward (dq and dk/dv
kernels driven by the saved per-row logsumexp, recomputing P blockwise
instead of storing T^2 probabilities).

Layout: q, k, v are (B, H, T, D). The grid walks (batch, head, q-block)
in parallel and the kv-block dimension sequentially ("arbitrary"), with
running max / sum / output accumulators living in VMEM scratch across the
kv sweep — the standard TPU flash schedule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params, on_tpu

_NEG_INF = -1e30  # finite stand-in for -inf: avoids inf-inf=nan in rescaling


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, offset):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal (bottom-right aligned, matching _sdpa_xla's tril(tk-tq)):
    # skip kv blocks entirely above the shifted diagonal
    run = (iq * block_q + block_q - 1 + offset >= ik * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row + offset, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l_safe)


# -- BTHD (all heads per block, flat lanes): the qkv projections emit
# (B, T, H, D); tiling that layout natively means NO transpose ops in the
# graph, and at long sequence the four per-layer transposes cost more HBM
# bandwidth than the attention itself. The kernels take q/k/v FLAT as
# (B, T, H*D) — a free reshape — because a 4D (…, H, D) operand forces a
# padded (16, 128)-tiled copy of every operand/output around the custom
# call (2.7x HBM traffic and a scoped-vmem OOM at batch 8), while
# (T, H*D) tiles dense. Heads live as 64-aligned lane slices; the
# per-head loop is statically unrolled (this mosaic build rejects batch
# dims in dot_general). Row stats (lse/delta) are (B, H, T) f32 — dense,
# vs the 128x lane padding a trailing-1 dim would cost.


def _fwd_kernel_bthd(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale, causal, block_q, block_k, offset, H):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # three block classes: skipped (above the causal diagonal), interior
    # (fully below it — NO mask arithmetic, the dominant class), and
    # diagonal-crossing (masked). The split halves the VPU work of the
    # interior blocks; the scale is folded into q once per block instead
    # of into every (BQ, BK) score tile.
    if causal:
        run = iq * block_q + block_q - 1 + offset >= ik * block_k
        full = ik * block_k + block_k - 1 <= iq * block_q + offset
    else:
        run, full = True, True

    def _compute(masked):
        if masked:
            shp = (block_q, block_k)
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shp, 0)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shp, 1)
            keep = col <= row + offset
        kv, vv = k_ref[0], v_ref[0]  # (BK, H*D)
        qv = (q_ref[0].astype(jnp.float32) * scale).astype(k_ref.dtype)
        for h in range(H):
            q = qv[:, h * D:(h + 1) * D]  # (BQ, D)
            k = kv[:, h * D:(h + 1) * D]  # (BK, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BQ, BK)
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_scr[:, h:h + 1]
            l_prev = l_scr[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vv.dtype), vv[:, h * D:(h + 1) * D],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sl = slice(h * D, (h + 1) * D)
            acc_scr[:, sl] = acc_scr[:, sl] * alpha + pv
            m_scr[:, h:h + 1] = m_new
            l_scr[:, h:h + 1] = l_new

    if causal:
        @pl.when(run & ~full)
        def _compute_masked():
            _compute(True)

        @pl.when(full)
        def _compute_full():
            _compute(False)
    else:
        @pl.when(run)
        def _compute_all():
            _compute(False)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :H]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # (BQ, H)
        lse_ref[0] = jnp.swapaxes(
            m_scr[:, :H] + jnp.log(l_safe), 0, 1)  # (H, BQ)
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            o_ref[0, :, sl] = (acc_scr[:, sl] / l_safe[:, h:h + 1]).astype(o_ref.dtype)


def _specs(bq, bk, D, swap_grid=False):
    """BHTD BlockSpecs for (q-tile, k-tile, row-stat-tile). swap_grid
    flips the last two grid axes (the dkv kernel walks kv blocks in
    parallel, q blocks sequentially)."""
    if swap_grid:
        qi = lambda b, h, ik, iq: iq
        ki = lambda b, h, ik, iq: ik
    else:
        qi = lambda b, h, iq, ik: iq
        ki = lambda b, h, iq, ik: ik
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, qi(b, h, i, j), 0))
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, ki(b, h, i, j), 0))
    rspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, qi(b, h, i, j), 0))
    return qspec, kspec, rspec


def _specs_bthd(bq, bk, H, D, swap_grid=False):
    """Flat-BTHD BlockSpecs over (B, T, H*D) operands and (B, H, T) row
    stats: grid is (B, nq, nk) [or (B, nk, nq) swapped]; every block
    carries all H heads as dense 64-aligned lane slices (see the layout
    rationale above _fwd_kernel_bthd)."""
    if swap_grid:
        qi = lambda b, ik, iq: iq
        ki = lambda b, ik, iq: ik
    else:
        qi = lambda b, iq, ik: iq
        ki = lambda b, iq, ik: ik
    qspec = pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, qi(b, i, j), 0))
    kspec = pl.BlockSpec((1, bk, H * D), lambda b, i, j: (b, ki(b, i, j), 0))
    rspec = pl.BlockSpec((1, H, bq), lambda b, i, j: (b, 0, qi(b, i, j)))
    return qspec, kspec, rspec


def _dims(q, k, bthd):
    if bthd:
        B, T, H, D = q.shape
        return B, H, T, D, k.shape[1]
    B, H, T, D = q.shape
    return B, H, T, D, k.shape[2]


def _fwd(q, k, v, *, causal, scale, block_q, block_k, interpret, bthd=False):
    B, H, T, D, Tk = _dims(q, k, bthd)
    bq, bk = min(block_q, T), min(block_k, Tk)
    nq, nk = T // bq, Tk // bk
    if bthd:
        # flatten heads onto lanes: free reshape, dense tiling (see the
        # layout rationale above _fwd_kernel_bthd)
        q = q.reshape(B, T, H * D)
        k = k.reshape(B, Tk, H * D)
        v = v.reshape(B, Tk, H * D)
        kernel = functools.partial(
            _fwd_kernel_bthd, scale=scale, causal=causal, block_q=bq,
            block_k=bk, offset=Tk - T, H=H,
        )
        qspec, kspec, rspec = _specs_bthd(bq, bk, H, D)
        grid = (B, nq, nk)
        lse_shape = (B, H, T)
        dims = ("parallel", "parallel", "arbitrary")
        if H > 128:
            raise ValueError(f"BTHD flash kernel supports at most 128 heads, got {H}")
        # row stats live one LANE per head ((bq, 128) f32) — the previous
        # (bq, H*128) broadcast layout burned 3MB of VMEM and a 128x
        # redundant write per head per kv block, and pushed the
        # (256, 1024)-block config 40KB over the 16MB scoped-vmem limit
        scratch = [
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, H * D), jnp.float32),
        ]
    else:
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            offset=Tk - T,
        )
        qspec, kspec, rspec = _specs(bq, bk, D)
        grid = (B, H, nq, nk)
        lse_shape = (B, H, T, 1)
        dims = ("parallel", "parallel", "parallel", "arbitrary")
        scratch = [
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, rspec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=compiler_params(dims),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    if bthd:
        out = out.reshape(B, T, H, D)
    return out, lse


# ---------------------------------------------------------------- backward


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, offset):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (iq * block_q + block_q - 1 + offset >= ik * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row + offset, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])  # [BQ, BK]
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dq_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr, *, scale, causal, block_q, block_k,
                        offset, H):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    # same block-class split as the forward: interior blocks skip the
    # mask arithmetic. Both scale multiplies are folded out of the
    # (BQ, BK) tiles: the first into q, the second into the dq finish.
    if causal:
        run = iq * block_q + block_q - 1 + offset >= ik * block_k
        full = ik * block_k + block_k - 1 <= iq * block_q + offset
    else:
        run, full = True, True

    def _compute(masked):
        if masked:
            shp = (block_q, block_k)
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shp, 0)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shp, 1)
            keep = col <= row + offset
        kv, vv, dov = k_ref[0], v_ref[0], do_ref[0]
        qv = (q_ref[0].astype(jnp.float32) * scale).astype(k_ref.dtype)
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            q, k = qv[:, sl], kv[:, sl]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            lse_col = jnp.swapaxes(lse_ref[0, h:h + 1, :], 0, 1)  # (BQ, 1)
            p = jnp.exp(s - lse_col)
            do = dov[:, sl]
            dp = jax.lax.dot_general(
                do, vv[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            delta_col = jnp.swapaxes(delta_ref[0, h:h + 1, :], 0, 1)
            ds = p * (dp - delta_col)
            dq_scr[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if causal:
        @pl.when(run & ~full)
        def _compute_masked():
            _compute(True)

        @pl.when(full)
        def _compute_full():
            _compute(False)
    else:
        @pl.when(run)
        def _compute_all():
            _compute(False)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, offset):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (iq * block_q + block_q - 1 + offset >= ik * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row + offset, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])  # [BQ, BK]
        do = do_ref[0, 0]
        # dv += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0]) * scale
        # dk += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr,
                         *, scale, causal, block_q, block_k, offset, H):
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:
        run = iq * block_q + block_q - 1 + offset >= ik * block_k
        full = ik * block_k + block_k - 1 <= iq * block_q + offset
    else:
        run, full = True, True

    def _compute(masked):
        # k-major orientation: every product is a standard (M,K)x(K,N)
        # matmul — dim-0 contractions over strided-read tiles crash this
        # mosaic build, so P/dS are built transposed as (BK, BQ) instead
        # of transposing them at the accumulate; the (B, H, T) row-stat
        # layout hands lse/delta over as ready-made (1, BQ) rows.
        # Scale folding: q arrives pre-scaled, so st is already scaled
        # and dk += dS_noscale @ (q*scale) bakes the second multiply in.
        if masked:
            shp = (block_k, block_q)
            col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shp, 0)
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shp, 1)
            keep = col <= row + offset
        kv, vv, dov = k_ref[0], v_ref[0], do_ref[0]
        qv = (q_ref[0].astype(jnp.float32) * scale).astype(k_ref.dtype)
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            q, k = qv[:, sl], kv[:, sl]
            # (BK, BQ) = K Q'^T  (already scaled via q')
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if masked:
                st = jnp.where(keep, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, h:h + 1, :])  # (BK, BQ)
            do = dov[:, sl]
            # dv += P^T dO
            dv_scr[:, sl] += jax.lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # (BK, BQ) = V dO^T
            dpt = jax.lax.dot_general(
                vv[:, sl], do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dst = pt * (dpt - delta_ref[0, h:h + 1, :])
            # dk += dS^T Q' (scale folded via q')
            dk_scr[:, sl] += jax.lax.dot_general(
                dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if causal:
        @pl.when(run & ~full)
        def _compute_masked():
            _compute(True)

        @pl.when(full)
        def _compute_full():
            _compute(False)
    else:
        @pl.when(run)
        def _compute_all():
            _compute(False)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, scale, block_q, block_k, interpret, bthd, bwd_blocks,
         res, do):
    """bwd_blocks = (bq_dq, bk_dq, bq_dkv, bk_dkv): the two backward
    passes CAN tile independently — the dq pass keeps a (bq, H*D)
    accumulator resident and sweeps kv sequentially, the dkv pass keeps
    (bk, H*D) accumulators and sweeps q. Measured on v5e @ T=2048
    (end-to-end GPT step, round 4): every decoupled candidate LOST to the
    shared (256,512) tiling — (128,1024;1024,128) 202ms,
    (128,512;512,128) 208ms, (256,1024;512,256) 196ms vs 194.5ms — the
    128-tall blocks underfeed the MXU at H*D=768. Default (None) keeps
    the forward tiling; the knob stays for re-sweeping on other chips."""
    q, k, v, out, lse = res
    B, H, T, D, Tk = _dims(q, k, bthd)
    bq_dq, bk_dq, bq_dkv, bk_dkv = bwd_blocks or (
        block_q, block_k, block_q, block_k
    )
    bq, bk = min(bq_dq, T), min(bk_dq, Tk)
    nq, nk = T // bq, Tk // bk

    if bthd:
        # (B, H, T) row stats to match the lse layout (see _specs_bthd)
        delta = jnp.transpose(
            jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1),
            (0, 2, 1),
        )
        q = q.reshape(B, T, H * D)
        k = k.reshape(B, Tk, H * D)
        v = v.reshape(B, Tk, H * D)
        do = do.reshape(B, T, H * D)
    else:
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
        )

    if bthd:
        qspec, kspec, rspec = _specs_bthd(bq, bk, H, D)
        dq_grid = (B, nq, nk)
        dims3 = ("parallel", "parallel", "arbitrary")
        dq_kernel, dkv_kernel = _bwd_dq_kernel_bthd, _bwd_dkv_kernel_bthd
        dq_scratch = [pltpu.VMEM((bq, H * D), jnp.float32)]
    else:
        qspec, kspec, rspec = _specs(bq, bk, D)
        dq_grid = (B, H, nq, nk)
        dims3 = ("parallel", "parallel", "parallel", "arbitrary")
        dq_kernel, dkv_kernel = _bwd_dq_kernel, _bwd_dkv_kernel
        dq_scratch = [pltpu.VMEM((bq, D), jnp.float32)]
    extra = {"H": H} if bthd else {}
    dq = pl.pallas_call(
        functools.partial(
            dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            offset=Tk - T, **extra,
        ),
        grid=dq_grid,
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=dq_scratch,
        compiler_params=compiler_params(dims3),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)[0]

    # kv sweep: grid walks kv blocks in parallel, q blocks sequentially
    bq, bk = min(bq_dkv, T), min(bk_dkv, Tk)
    nq, nk = T // bq, Tk // bk
    if bthd:
        dkv_scratch = [
            pltpu.VMEM((bk, H * D), jnp.float32),
            pltpu.VMEM((bk, H * D), jnp.float32),
        ]
        qspec2, kspec2, rspec2 = _specs_bthd(bq, bk, H, D, swap_grid=True)
        dkv_grid = (B, nk, nq)
    else:
        dkv_scratch = [
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ]
        qspec2, kspec2, rspec2 = _specs(bq, bk, D, swap_grid=True)
        dkv_grid = (B, H, nk, nq)
    dk, dv = pl.pallas_call(
        functools.partial(
            dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            offset=Tk - T, **extra,
        ),
        grid=dkv_grid,
        in_specs=[qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=dkv_scratch,
        compiler_params=compiler_params(dims3),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    if bthd:
        dq = dq.reshape(B, T, H, D)
        dk = dk.reshape(B, Tk, H, D)
        dv = dv.reshape(B, Tk, H, D)
    return dq, dk, dv


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, bthd,
           bwd_blocks):
    out, _ = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, bthd=bthd,
    )
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, bthd,
               bwd_blocks):
    out, lse = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, bthd=bthd,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, bthd, bwd_blocks,
               res, do):
    return _bwd(causal, scale, block_q, block_k, interpret, bthd,
                bwd_blocks, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=256, block_k=256, interpret=None,
                    layout="BHTD", bwd_blocks=None):
    """Blocked flash attention. q,k,v: (B, H, T, D) for layout='BHTD' or
    (B, T, H, D) for layout='BTHD'; the output matches the input layout.
    Native BTHD tiling means the qkv projections feed the kernel without
    any transpose ops — at long sequence the transposes dominate the
    attention cost itself.

    Differentiable (flash backward kernels). Sequence lengths must divide
    the block sizes (the dispatcher in ops/attention.py guarantees this or
    selects the XLA path from the shape). On non-TPU backends runs the
    pallas interpreter, so tests on the virtual CPU mesh exercise the
    same code.
    """
    bthd = layout == "BTHD"
    B, H, T, D, Tk = _dims(q, k, bthd)
    bq, bk = min(block_q, T), min(block_k, Tk)
    if T % bq or Tk % bk:
        raise ValueError(f"seq lengths ({T},{Tk}) must divide blocks ({bq},{bk})")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = not on_tpu()
    if bwd_blocks is not None:
        bwd_blocks = tuple(min(int(b), (Tk if i % 2 else T))
                           for i, b in enumerate(bwd_blocks))
        if (T % bwd_blocks[0] or Tk % bwd_blocks[1]
                or T % bwd_blocks[2] or Tk % bwd_blocks[3]):
            raise ValueError(
                f"seq lengths ({T},{Tk}) must divide bwd_blocks {bwd_blocks}")
    return _flash(q, k, v, causal, float(scale), bq, bk, bool(interpret),
                  bthd, bwd_blocks)
