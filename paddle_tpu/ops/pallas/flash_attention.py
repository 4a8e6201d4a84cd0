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

from ... import monitor as _monitor
from .backend import compiler_params, on_tpu

_NEG_INF = -1e30  # finite stand-in for -inf: avoids inf-inf=nan in rescaling

_M_TILES = _monitor.counter(
    "flash_tiles_total",
    "score tiles (squares of a grid tile's smaller side) of the flash "
    "kernels by what the causal schedule does with them: skipped (above "
    "the diagonal: no work, no copy, or trimmed off a tile that crosses "
    "it), interior (below it, no mask arithmetic), diagonal (masked). "
    "Counted over a call's whole grid when the kernel is traced, not per "
    "step",
    labelnames=("kernel", "cls"))
_KERNELS, _CLASSES = ("fwd", "dq", "dkv"), ("skipped", "interior", "diagonal")


def tile_counts():
    """{kernel: {cls: flash_tiles_total as it stands}}, for the tools and
    tests that read a share of the score square from it."""
    return {k: {c: _M_TILES.labels(kernel=k, cls=c).value for c in _CLASSES}
            for k in _KERNELS}


# ------------------------------------------------- the causal tile schedule
#
# A (bq, bk) score tile at grid position (iq, ik) is one of three classes
# (bottom-right aligned mask, offset = Tk - T, as _sdpa_xla's tril):
#   skipped   every column lies above the diagonal: no arithmetic, and the
#             index maps below fetch nothing for it
#   interior  every column lies at or below it: no mask arithmetic
#   diagonal  crosses it: masked, and computed only as far as the diagonal
#             reaches into it (_trims)
# A kernel's time is the score area it computes plus a cost per accumulator
# row and step of its sequential sweep (running max and sum, rescale, row
# statistics), so the dispatcher's tiles are wide along the sweep and the
# saving under the mask comes from trimming, not from many small tiles
# (ops/attention.py::_FLASH_TILES). A non-causal call has interior tiles
# only.


def _tile_classes(nq, nk, bq, bk, offset, causal, trims):
    """(skipped, interior, diagonal) of one (nq, nk) grid of score tiles,
    counted in squares of the tile's smaller side, so that what a tile
    crossing the diagonal leaves out (`trims`) counts as skipped."""
    g = min(bq, bk)
    per_tile = max(bq, bk) // g
    if not causal:
        return 0, nq * nk * per_tile, 0
    interior = diagonal = 0
    for iq in range(nq):
        for ik in range(nk):
            cross = iq * bq + offset - ik * bk
            if cross >= bk - 1:
                interior += per_tile
            elif cross + bq - 1 >= 0:
                r0, r1, c0, c1 = trims[abs(cross) // g] if len(trims) > 1 else trims[0]
                diagonal += (r1 - r0) * (c1 - c0) // g ** 2
    return nq * nk * per_tile - interior - diagonal, interior, diagonal


def _count_tiles(kernel, q, k, bthd, block_q, block_k, causal):
    """Bump flash_tiles_total for one call of `kernel` as it is traced into
    a program: every score grid of the call (batch, and heads where the
    grid walks them)."""
    B, H, T, _, Tk = _dims(q, k, bthd)
    bq, bk = min(block_q, T), min(block_k, Tk)
    nq, nk = T // bq, Tk // bk
    trims = _trims(bq, bk, Tk - T, nq if kernel == "dkv" else nk)
    classes = _tile_classes(nq, nk, bq, bk, Tk - T, causal, trims)
    for cls, n in zip(_CLASSES, classes):
        _M_TILES.labels(kernel=kernel, cls=cls).inc((B if bthd else B * H) * n)


def _tile_index(causal, swap_grid, bq, bk, nq, nk, offset):
    """(qi, ki): the q and kv tile an operand's BlockSpec fetches at the
    last two grid coordinates (i, j); j is the sequential sweep (kv tiles
    for forward and dq; q tiles for dkv, `swap_grid`). Under the causal
    mask the sweep's index is clamped to the last kv tile the q rows need
    (first q tile the kv columns need), so a skipped step names the block
    its neighbour already holds and Pallas issues no copy for it. A
    non-causal call keeps the bare indices."""
    if not causal:
        if swap_grid:
            return (lambda i, j: j), (lambda i, j: i)
        return (lambda i, j: i), (lambda i, j: j)

    def last_k(iq):
        need = jnp.maximum(iq * bq + (bq - 1 + offset), 0)
        return jnp.minimum(jax.lax.div(need, bk), nk - 1)

    def first_q(ik):
        need = jnp.maximum(ik * bk - offset, 0)
        return jnp.minimum(jax.lax.div(need, bq), nq - 1)

    if swap_grid:
        return (lambda i, j: jnp.maximum(j, first_q(i))), (lambda i, j: i)
    return (lambda i, j: i), (lambda i, j: jnp.minimum(j, last_k(i)))


def _trims(block_q, block_k, offset, steps):
    """The parts (r0, r1, c0, c1) of a (bq, bk) tile that a tile crossing
    the diagonal can need, one per position it can cross at. Where one
    side divides the other and the offset is whole small sides, a wide
    tile (bk > bq) crosses at iq*bq + offset - ik*bk = j*bq and needs its
    first (j + 1)*bq columns; a tall one (bq > bk) crosses at -j*bk and
    needs its rows from j*bk on. The rest of such a tile is all mask and
    is not computed. A square tile, or one whose crossing is not aligned,
    has the one part: all of it. Each part is one more copy of the
    kernel's unrolled body: with more than two, a kernel whose sequential
    sweep has several `steps` ran three times slower than untrimmed (v5e,
    T 2048, PR 35), so there the tile stays whole too."""
    g = min(block_q, block_k)
    whole = [(0, block_q, 0, block_k)]
    if block_q == block_k or max(block_q, block_k) % g or offset % g:
        return whole
    if steps > 1 and max(block_q, block_k) // g > 2:
        return whole
    if block_k > block_q:
        return [(0, block_q, 0, (j + 1) * g) for j in range(block_k // g)]
    return [(j * g, block_q, 0, block_k) for j in range(block_q // g)]


def _run_by_class(compute, iq, ik, block_q, block_k, offset, causal, trims):
    """Run `compute(masked, part)` for the tile at (iq, ik) as its class
    says: not at all, whole and without mask arithmetic, or masked and
    trimmed to the part under the diagonal (`trims`)."""
    whole = (0, block_q, 0, block_k)
    if not causal:  # under pl.when, as such a call has always traced
        pl.when(True)(lambda: compute(False, whole))
        return
    cross = iq * block_q + offset - ik * block_k  # where the diagonal enters the tile
    run, full = cross + block_q - 1 >= 0, cross >= block_k - 1
    pl.when(full)(lambda: compute(False, whole))
    if len(trims) == 1:
        pl.when(run & ~full)(lambda: compute(True, whole))
        return
    j = jax.lax.div(jnp.abs(cross), min(block_q, block_k))
    for n, part in enumerate(trims):
        pl.when(run & ~full & (j == n))(functools.partial(compute, True, part))


def _keep(iq, ik, block_q, block_k, offset, part, k_major=False):
    """The mask of one part of the tile at (iq, ik): (rows, columns), or
    (columns, rows) for the k-major kernels."""
    r0, r1, c0, c1 = part
    shp = (c1 - c0, r1 - r0) if k_major else (r1 - r0, c1 - c0)
    row = iq * block_q + r0 + jax.lax.broadcasted_iota(jnp.int32, shp, 1 if k_major else 0)
    col = ik * block_k + c0 + jax.lax.broadcasted_iota(jnp.int32, shp, 0 if k_major else 1)
    return col <= row + offset


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, offset, trims):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked, part):
        rows, cols = slice(*part[:2]), slice(*part[2:])
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if masked:
            s = jnp.where(_keep(iq, ik, block_q, block_k, offset, part), s, _NEG_INF)

        m_prev = m_scr[rows, :1]
        l_prev = l_scr[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[rows] = acc_scr[rows] * alpha + pv
        m_scr[rows] = jnp.broadcast_to(m_new, (q.shape[0], m_scr.shape[1]))
        l_scr[rows] = jnp.broadcast_to(l_new, (q.shape[0], l_scr.shape[1]))

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

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
                     acc_scr, *, scale, causal, block_q, block_k, offset, trims,
                     H, single):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    # `single`: the kv sweep is one step that reaches every q row, so the
    # running max / sum / accumulator start and end in it: nothing to
    # initialise, read back or rescale.
    if not single:
        @pl.when(ik == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    # interior tiles (the dominant class) skip the mask arithmetic, which
    # halves their VPU work; the scale is folded into q once per block
    # instead of into every (BQ, BK) score tile.
    def _compute(masked, part):
        rows, cols = slice(*part[:2]), slice(*part[2:])
        if masked:
            keep = _keep(iq, ik, block_q, block_k, offset, part)
        kv, vv = k_ref[0, cols], v_ref[0, cols]  # (BK, H*D)
        qv = (q_ref[0, rows].astype(jnp.float32) * scale).astype(k_ref.dtype)
        for h in range(H):
            q = qv[:, h * D:(h + 1) * D]  # (BQ, D)
            k = kv[:, h * D:(h + 1) * D]  # (BK, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (BQ, BK)
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            sl = slice(h * D, (h + 1) * D)
            pv_of = lambda p: jax.lax.dot_general(  # noqa: E731
                p.astype(vv.dtype), vv[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if single:
                m_new = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m_new)
                l_new = jnp.sum(p, axis=-1, keepdims=True)
                acc_scr[rows, sl] = pv_of(p)
            else:
                m_prev = m_scr[rows, h:h + 1]
                l_prev = l_scr[rows, h:h + 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
                pv = pv_of(p)
                acc_scr[rows, sl] = acc_scr[rows, sl] * alpha + pv
            m_scr[rows, h:h + 1] = m_new
            l_scr[rows, h:h + 1] = l_new

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :H]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # (BQ, H)
        lse_ref[0] = jnp.swapaxes(
            m_scr[:, :H] + jnp.log(l_safe), 0, 1)  # (H, BQ)
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            o_ref[0, :, sl] = (acc_scr[:, sl] / l_safe[:, h:h + 1]).astype(o_ref.dtype)


def _specs(bq, bk, D, index):
    """BHTD BlockSpecs for (q-tile, k-tile, row-stat-tile); `index` is
    _tile_index's (qi, ki) over the last two grid axes."""
    qi, ki = index
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, qi(i, j), 0))
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, ki(i, j), 0))
    rspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, qi(i, j), 0))
    return qspec, kspec, rspec


def _specs_bthd(bq, bk, H, D, index):
    """Flat-BTHD BlockSpecs over (B, T, H*D) operands and (B, H, T) row
    stats: grid is (B, nq, nk) [or (B, nk, nq) for dkv]; every block
    carries all H heads as dense 64-aligned lane slices (see the layout
    rationale above _fwd_kernel_bthd). `index` as in _specs."""
    qi, ki = index
    qspec = pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, qi(i, j), 0))
    kspec = pl.BlockSpec((1, bk, H * D), lambda b, i, j: (b, ki(i, j), 0))
    rspec = pl.BlockSpec((1, H, bq), lambda b, i, j: (b, 0, qi(i, j)))
    return qspec, kspec, rspec


def _dims(q, k, bthd):
    if bthd:
        B, T, H, D = q.shape
        return B, H, T, D, k.shape[1]
    B, H, T, D = q.shape
    return B, H, T, D, k.shape[2]


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret", "bthd"))
def _fwd(q, k, v, *, causal, scale, block_q, block_k, interpret, bthd=False):
    B, H, T, D, Tk = _dims(q, k, bthd)
    bq, bk = min(block_q, T), min(block_k, Tk)
    nq, nk = T // bq, Tk // bk
    index = _tile_index(causal, False, bq, bk, nq, nk, Tk - T)
    trims = tuple(_trims(bq, bk, Tk - T, nk))
    if bthd:
        # flatten heads onto lanes: free reshape, dense tiling (see the
        # layout rationale above _fwd_kernel_bthd)
        q = q.reshape(B, T, H * D)
        k = k.reshape(B, Tk, H * D)
        v = v.reshape(B, Tk, H * D)
        kernel = functools.partial(
            _fwd_kernel_bthd, scale=scale, causal=causal, block_q=bq,
            block_k=bk, offset=Tk - T, trims=trims, H=H,
            single=nk == 1 and bk >= bq and Tk >= T,
        )
        qspec, kspec, rspec = _specs_bthd(bq, bk, H, D, index)
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
            offset=Tk - T, trims=trims,
        )
        qspec, kspec, rspec = _specs(bq, bk, D, index)
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
                   dq_scr, *, scale, causal, block_q, block_k, offset, trims):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked, part):
        rows, cols = slice(*part[:2]), slice(*part[2:])
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = jnp.where(_keep(iq, ik, block_q, block_k, offset, part), s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, rows])  # [BQ, BK]
        do = do_ref[0, 0, rows]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, rows]) * scale
        dq_scr[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dq_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_scr, *, scale, causal, block_q, block_k,
                        offset, trims, H, single):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    # `single` (as in the forward): dq is whole after the one kv step and
    # is written where it goes, with no accumulator in between
    if not single:
        @pl.when(ik == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    # Both scale multiplies are folded out of the (BQ, BK) tiles: the
    # first into q, the second into the dq finish.
    def _compute(masked, part):
        rows, cols = slice(*part[:2]), slice(*part[2:])
        if masked:
            keep = _keep(iq, ik, block_q, block_k, offset, part)
        kv, vv, dov = k_ref[0, cols], v_ref[0, cols], do_ref[0, rows]
        qv = (q_ref[0, rows].astype(jnp.float32) * scale).astype(k_ref.dtype)
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            q, k = qv[:, sl], kv[:, sl]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            lse_col = jnp.swapaxes(lse_ref[0, h:h + 1, rows], 0, 1)  # (BQ, 1)
            p = jnp.exp(s - lse_col)
            do = dov[:, sl]
            dp = jax.lax.dot_general(
                do, vv[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            delta_col = jnp.swapaxes(delta_ref[0, h:h + 1, rows], 0, 1)
            ds = p * (dp - delta_col)
            dq_of = lambda: jax.lax.dot_general(  # noqa: E731
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if single:
                dq_ref[0, rows, sl] = (dq_of() * scale).astype(dq_ref.dtype)
            else:
                dq_scr[rows, sl] += dq_of()

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

    if not single:
        @pl.when(ik == nk - 1)
        def _finish():
            dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, offset, trims):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked, part):
        rows, cols = slice(*part[:2]), slice(*part[2:])
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            s = jnp.where(_keep(iq, ik, block_q, block_k, offset, part), s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, rows])  # [BQ, BK]
        do = do_ref[0, 0, rows]
        # dv += P^T dO
        dv_scr[cols] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, rows]) * scale
        # dk += dS^T Q
        dk_scr[cols] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel_bthd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr,
                         *, scale, causal, block_q, block_k, offset, trims,
                         H, single):
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    D = q_ref.shape[-1] // H

    # `single`: the q sweep is one step that reaches every kv column
    if not single:
        @pl.when(iq == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked, part):
        # k-major orientation: every product is a standard (M,K)x(K,N)
        # matmul — dim-0 contractions over strided-read tiles crash this
        # mosaic build, so P/dS are built transposed as (BK, BQ) instead
        # of transposing them at the accumulate; the (B, H, T) row-stat
        # layout hands lse/delta over as ready-made (1, BQ) rows.
        # Scale folding: q arrives pre-scaled, so st is already scaled
        # and dk += dS_noscale @ (q*scale) bakes the second multiply in.
        rows, cols = slice(*part[:2]), slice(*part[2:])
        if masked:
            keep = _keep(iq, ik, block_q, block_k, offset, part, k_major=True)
        kv, vv, dov = k_ref[0, cols], v_ref[0, cols], do_ref[0, rows]
        qv = (q_ref[0, rows].astype(jnp.float32) * scale).astype(k_ref.dtype)
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
            pt = jnp.exp(st - lse_ref[0, h:h + 1, rows])  # (BK, BQ)
            do = dov[:, sl]
            # dv += P^T dO
            dv_of = lambda: jax.lax.dot_general(  # noqa: E731
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if single:
                dv_ref[0, cols, sl] = dv_of().astype(dv_ref.dtype)
            else:
                dv_scr[cols, sl] += dv_of()
            # (BK, BQ) = V dO^T
            dpt = jax.lax.dot_general(
                vv[:, sl], do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dst = pt * (dpt - delta_ref[0, h:h + 1, rows])
            # dk += dS^T Q' (scale folded via q')
            dk_of = lambda: jax.lax.dot_general(  # noqa: E731
                dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if single:
                dk_ref[0, cols, sl] = dk_of().astype(dk_ref.dtype)
            else:
                dk_scr[cols, sl] += dk_of()

    _run_by_class(_compute, iq, ik, block_q, block_k, offset, causal, trims)

    if not single:
        @pl.when(iq == nq - 1)
        def _finish():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _bwd(causal, scale, block_q, block_k, interpret, bthd, bwd_blocks,
         res, do):
    """bwd_blocks = (bq_dq, bk_dq, bq_dkv, bk_dkv): the two backward
    passes CAN tile independently: the dq pass keeps a (bq, H*D)
    accumulator resident and sweeps kv sequentially, the dkv pass keeps
    (bk, H*D) accumulators and sweeps q. None keeps the forward tiling.
    The dispatcher's table (ops/attention.py::_FLASH_TILES) names the
    tiles; PR 35 swept them on a TPU v5e at B 32, T 1024, 12 heads of 64,
    BTHD, causal (tools/flash_sweep.py, each kernel alone and then the
    whole train step; the numbers are in PERF.md section 6)."""
    q, k, v, out, lse = res
    B, H, T, D, Tk = _dims(q, k, bthd)
    bq_dq, bk_dq, bq_dkv, bk_dkv = bwd_blocks or (
        block_q, block_k, block_q, block_k
    )
    bq, bk = min(bq_dq, T), min(bk_dq, Tk)
    nq, nk = T // bq, Tk // bk
    index = _tile_index(causal, False, bq, bk, nq, nk, Tk - T)

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
        qspec, kspec, rspec = _specs_bthd(bq, bk, H, D, index)
        dq_grid = (B, nq, nk)
        dims3 = ("parallel", "parallel", "arbitrary")
        dq_kernel, dkv_kernel = _bwd_dq_kernel_bthd, _bwd_dkv_kernel_bthd
        dq_scratch = [pltpu.VMEM((bq, H * D), jnp.float32)]
    else:
        qspec, kspec, rspec = _specs(bq, bk, D, index)
        dq_grid = (B, H, nq, nk)
        dims3 = ("parallel", "parallel", "parallel", "arbitrary")
        dq_kernel, dkv_kernel = _bwd_dq_kernel, _bwd_dkv_kernel
        dq_scratch = [pltpu.VMEM((bq, D), jnp.float32)]
    # `single`: one step of the sequential sweep that reaches every
    # accumulator row (a tall tile's trims, and Tk < T, leave q rows out):
    # the BTHD kernels then skip the accumulator round trip
    extra = lambda single: {"H": H, "single": single} if bthd else {}  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(
            dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            offset=Tk - T, trims=tuple(_trims(bq, bk, Tk - T, nk)),
            **extra(nk == 1 and bk >= bq and Tk >= T),
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
    index = _tile_index(causal, True, bq, bk, nq, nk, Tk - T)
    if bthd:
        dkv_scratch = [
            pltpu.VMEM((bk, H * D), jnp.float32),
            pltpu.VMEM((bk, H * D), jnp.float32),
        ]
        qspec2, kspec2, rspec2 = _specs_bthd(bq, bk, H, D, index)
        dkv_grid = (B, nk, nq)
    else:
        dkv_scratch = [
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ]
        qspec2, kspec2, rspec2 = _specs(bq, bk, D, index)
        dkv_grid = (B, H, nk, nq)
    dk, dv = pl.pallas_call(
        functools.partial(
            dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            offset=Tk - T, trims=tuple(_trims(bq, bk, Tk - T, nq)),
            **extra(nq == 1 and bq >= bk),
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


# _fwd and _bwd are jitted, so the layers of a model that call them at one
# shape share ONE trace and ONE Mosaic lowering of each kernel (36 lowerings
# of a 12-layer step became 3); the rules below run once a call site, which
# is where flash_tiles_total is counted.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, bthd,
           bwd_blocks):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      bthd, bwd_blocks)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, bthd,
               bwd_blocks):
    _count_tiles("fwd", q, k, bthd, block_q, block_k, causal)
    out, lse = _fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, bthd=bthd,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, bthd, bwd_blocks,
               res, do):
    q, k = res[:2]
    tiles = bwd_blocks or (block_q, block_k, block_q, block_k)
    _count_tiles("dq", q, k, bthd, tiles[0], tiles[1], causal)
    _count_tiles("dkv", q, k, bthd, tiles[2], tiles[3], causal)
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
