"""Fused lm-head + softmax cross-entropy as pallas TPU kernels.

The raw-speed round's tentpole (ROADMAP item 2): OPBENCH_r05 shows the
two ops that dwarf the GPT step are ``matmul_lmhead`` (~6.4ms) and
``softmax_with_cross_entropy`` (~3.1ms) — and most of the CE cost is not
compute but the [tokens, vocab] logits tensor's HBM round-trip (bf16
logits at B*T=16384, V=32768 are 1GB written by the matmul and read
straight back by the softmax, twice more in the backward). The chunked
``fused_lm_head_ce`` lax-loop (ops/fused_ops.py) already avoids holding
every chunk at once but still materializes one [C, V] tile per step of a
*sequential* scan, at 1.1 GB more peak HBM on the benchmark's GPT-2
small cell (PR 40's A/B: PERF.md section 6).

Here the whole loss is one flash-style kernel family of two kernels
(PR 40; three until then: the backward ran as a dx and a dw kernel that
each formed the logits tile again):

- forward (``lmhead_ce_stats``): a blocked online-softmax sweep over
  vocab tiles. For each token block the kernel walks the vocab tiles
  and keeps a running (max, sum-exp) PER LANE in VMEM scratch: a tile's
  128-column chunks fold into it elementwise, and the one cross-lane
  reduction a row needs runs at the last tile. It writes two f32 row
  stats per token; the (block_n, block_v) logits tile lives in VMEM
  only, *never* in HBM. The logit at the label is not picked out of the
  tiles: it is the f32 row dot ``x . w[label]`` beside the kernel;
- backward (custom VJP, ``lmhead_ce_dw``: the name the benchmark's
  pattern admits; it gives dx AND dW): ONE kernel rematerializes each
  logits tile from the saved per-row logsumexp (the flash backward
  pattern of flash_attention.py), forms ``dl = (softmax - onehot) * g``
  once and feeds both products from it: three matmuls a tile. The grid
  is (token blocks, vocab tiles), both sequential. dx accumulates in a
  (block_n, D) f32 VMEM scratch across the vocab sweep; the f32 dW
  accumulator (156 MB at GPT-2 small's vocabulary) cannot stay in VMEM,
  so it lives in HBM and the kernel copies a (block_v, D) block of it
  in, adds and copies it back around each tile's matmuls
  (``make_async_copy``, two staging buffers). ``dW``/``dx`` are
  accumulated in f32 and cast once at the end;
- the vocab is padded up to a tile multiple, and only the LAST vocab
  tile holds padded columns: both kernels carry the mask's compare and
  select in a last-tile branch only;
- tiles follow the call's (tokens, width) and a VMEM budget
  (``tiles``); ``block_n`` / ``block_v`` are explicit overrides.

Memory math (the README "Raw speed" section walks this): the naive path
holds tokens*vocab logits (+ the same again as the backward's d_logits);
the pallas path holds 2*tokens f32 of row stats — at the bench shapes
that is 1GB+ vs 128KB, and the AOT ``memory_analysis`` peak of the
``lmhead_ce_fused_pallas`` OPBENCH row proves it.

Tensor-parallel composition: under the recipe table's tp axis the
lm-head weight (``gpt.wte``) is vocab-sharded (``GPT_TP_RULES``), so
:func:`lmhead_ce_sharded` runs the same kernel per shard inside a
``shard_map`` region — each device computes partial (max, sum-exp,
picked) stats over its vocab shard, one pmax + one psum combine them
across the tp axis, and the backward psums the partial ``dx`` (``dW``
stays shard-local). Batch axes (dp/fsdp) shard the token rows with no
collective; an fsdp-sharded weight (tp=1) is gathered at use, the same
2x-gather convention the recipe's analytic plan already prices.

On non-TPU backends the kernels run under the pallas interpreter
(``interpret=True``), so tier-1 exercises the same code path.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import shard_map

from .backend import compiler_params, on_tpu

_NEG_INF = -1e30  # finite stand-in for -inf (inf-inf = nan in rescaling)

# Tiles, preferred first, and what `_vmem_bytes` of a call's tiles may
# reach: swept on a TPU v5e by PR 40 with tools/ce_sweep.py (PERF.md
# section 6). Well inside the 64 MB a kernel may use (backend.VMEM_LIMIT)
# there is a cliff: at D 768 the backward takes 42.8 ms at (1024, 768)
# [29 MB by this count] and 66-67 at (1024, 1536) or (2048, 512) [49,
# 40]; at D 1600 (1,024 tokens) 4.2 ms at (1024, 256) [29], 4.4 at
# (512, 512) [24] and 5.5-5.9 at (1024, 512) [38]. The two widths
# measured pad GPT-2's 50,304 rows to the same 50,688.
_VMEM_BUDGET = 32 * 1024 * 1024
_TILES = ((1024, 768), (1024, 256), (512, 512), (256, 512), (256, 256),
          (256, 128))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _vmem_bytes(bn: int, bv: int, d: int, itemsize: int) -> int:
    """What the backward kernel, the larger of the two, keeps in VMEM at
    a tile: the x, dx and w blocks double-buffered, the f32 dx
    accumulator, two f32 staging buffers of a dW block, and the score
    tile's f32 temporaries (s, p, dl and one to spare)."""
    return (2 * 2 * bn * d * itemsize + 2 * bv * d * itemsize
            + 4 * bn * d + 2 * 4 * bv * d + 4 * 4 * bn * bv)


def tiles(n: int, d: int, v: int, itemsize: int = 2):
    """(block_n, block_v) for a call of ``n`` tokens, width ``d``, vocab
    ``v``: the first of ``_TILES`` under the VMEM budget at this width
    (every token block streams all of w again, and the backward's HBM
    accumulator once more, so tall blocks first), never larger than the
    call, and no token block that pads the call by more than 1/16."""
    bn, bv = next((t for t in _TILES
                   if _vmem_bytes(*t, d, itemsize) <= _VMEM_BUDGET),
                  _TILES[-1])
    n8 = _round_up(max(n, 1), 8)
    bns = [min(b, n8) for b in (1024, 512, 256) if b <= bn]
    bn = next((b for b in bns if (_round_up(n, b) - n) * 16 <= n), bns[-1])
    return bn, min(bv, _round_up(v, 128))


def _cost_kwargs(flops: int, bytes_accessed: int, transcendentals: int = 0):
    """Analytic pl.CostEstimate for the kernel: XLA's cost_analysis
    cannot see inside a custom call, so the kernel states its own FLOPs
    — what keeps achieved-MFU attribution (tools/xla_report.py) from
    reporting the lm-head as vanished compute."""
    return {"cost_estimate": pl.CostEstimate(
        flops=int(flops), transcendentals=int(transcendentals),
        bytes_accessed=int(bytes_accessed))}


def _scores(x_ref, w_ref):
    """The (BN, BV) f32 logits tile — VMEM only, never HBM."""
    return jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _columns(shape, iv, block_v):
    return iv * block_v + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _mask_last_tile(tile, iv, nv, ragged):
    """Run ``tile(masked)``. The vocab is padded up to a tile multiple
    and the padded columns must not reach the softmax, but only the LAST
    vocab tile holds any: every other tile runs the copy of the body
    without the compare and select."""
    if not ragged:
        tile(False)
        return
    pl.when(iv < nv - 1)(lambda: tile(False))
    pl.when(iv == nv - 1)(lambda: tile(True))


# ---------------------------------------------------------------- forward


def _stats_kernel(x_ref, w_ref, m_ref, l_ref, m_scr, l_scr,
                  *, block_v, v_total):
    """One token block x one vocab tile: online (max, sum-exp) update.
    The running statistics are kept PER LANE in (block_n, 128) scratch:
    a tile folds its 128-column chunks into them elementwise, and the
    one cross-lane reduction a row needs runs at the last vocab tile,
    not on every tile. Outputs are (1, block_n) row vectors."""
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def tile(masked):
        s = _scores(x_ref, w_ref)
        if masked:
            s = jnp.where(_columns(s.shape, iv, block_v) < v_total, s,
                          _NEG_INF)
        chunks = [s[:, c:c + 128] for c in range(0, block_v, 128)]
        m_prev = m_scr[...]
        m_new = functools.reduce(jnp.maximum, chunks, m_prev)
        l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) + sum(
            jnp.exp(c - m_new) for c in chunks)
        m_scr[...] = m_new

    _mask_last_tile(tile, iv, nv, v_total % block_v)

    @pl.when(iv == nv - 1)
    def _finish():
        # a lane that only ever saw padding holds (_NEG_INF, count): its
        # weight exp(_NEG_INF - m) is exactly 0
        m_lane = m_scr[...]
        m = jnp.max(m_lane, axis=-1, keepdims=True)
        l = jnp.sum(l_scr[...] * jnp.exp(m_lane - m), axis=-1,
                    keepdims=True)
        m_ref[...] = jnp.swapaxes(m, 0, 1)                # (1, BN)
        l_ref[...] = jnp.swapaxes(l, 0, 1)


def _stats_call(x2d, w, bn, bv, v_total, interpret):
    n, d = x2d.shape
    vp = w.shape[0]
    rspec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    stat = jax.ShapeDtypeStruct((1, n), jnp.float32)
    m, l = pl.pallas_call(
        functools.partial(_stats_kernel, block_v=bv, v_total=v_total),
        grid=(n // bn, vp // bv),
        in_specs=[pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bv, d), lambda i, j: (j, 0))],
        out_specs=[rspec, rspec],
        out_shape=[stat, stat],
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32)] * 2,
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="lmhead_ce_stats",
        **_cost_kwargs(2 * n * vp * d,
                       x2d.nbytes + (n // bn) * w.nbytes + 2 * 4 * n,
                       transcendentals=n * vp),
    )(x2d, w)
    return m[0], l[0]


def _picked(x2d, w, lbl):
    """The logit at each row's label as the f32 row dot x . w[label]
    (bf16 products are exact in f32, as out of the MXU): N*D work beside
    the kernel instead of a compare, select and row sum over every
    (block_n, block_v) tile. A label outside [0, V) — another shard's
    under vocab sharding — picks 0."""
    v = w.shape[0]
    ok = (lbl >= 0) & (lbl < v)
    rows = jnp.take(w, jnp.where(ok, lbl, 0), axis=0)
    pk = jnp.sum(x2d.astype(jnp.float32) * rows.astype(jnp.float32), axis=-1)
    return jnp.where(ok, pk, 0.0)


# ---------------------------------------------------------------- backward


def _bwd_kernel(x_ref, w_ref, lbl_ref, g_ref, lse_ref, dx_ref, dw_ref,
                *scratch, block_v, v_total, nn, nv):
    """dx = dl @ W and dW = dl^T @ X from ONE rematerialised tile
    dl = (softmax - onehot) * g, on a sequential (token blocks, vocab
    tiles) grid. dx accumulates in VMEM scratch across the vocab sweep.
    The f32 dW accumulator cannot stay in VMEM: it lives in HBM, and the
    kernel copies a (block_v, D) block of it in, adds to it and copies it
    back around each tile's matmuls, through two staging buffers. The
    first token block writes and does not add (no zeros to make), and a
    block's write-back is waited for two steps later, so it has landed
    before the block is read again ``nv`` >= 2 steps on. With one vocab
    tile the block never changes and stays a resident output block; with
    one token block nothing accumulates and dW is written as it comes."""
    i_n, iv = pl.program_id(0), pl.program_id(1)
    by_hand = nn > 1 and nv > 1
    if by_hand:
        *scratch, stage0, stage1, rd_sem, wr_sem = scratch
    step = i_n * nv + iv

    def on_stage(fn, of_step=step):
        """fn(staging buffer, read, write) with the copies HBM block ->
        buffer and back, for the buffer of a step. The two buffers are
        separate scratch arrays chosen by branch: Mosaic slices no
        buffer whose rows are not whole lane tiles (D 1600)."""
        block = dw_ref.at[pl.ds(iv * block_v, block_v)]
        for s, stage in enumerate((stage0, stage1)):
            pl.when(of_step % 2 == s)(functools.partial(
                fn, stage,
                lambda stage=stage, s=s: pltpu.make_async_copy(
                    block, stage, rd_sem.at[s]),
                lambda stage=stage, s=s: pltpu.make_async_copy(
                    stage, block, wr_sem.at[s])))

    if by_hand:
        def before(stage, read, write):
            @pl.when(step >= 2)
            def _():
                write().wait()  # the write-back of two steps ago

            @pl.when(i_n > 0)
            def _():
                read().start()

        on_stage(before)

    def tile(masked):
        w = w_ref[...]
        s = _scores(x_ref, w_ref)
        col = _columns(s.shape, iv, block_v)
        if masked:
            s = jnp.where(col < v_total, s, _NEG_INF)
        p = jnp.exp(s - jnp.swapaxes(lse_ref[...], 0, 1))
        hit = (col == lbl_ref[0][:, None]).astype(jnp.float32)
        g_col = jnp.swapaxes(g_ref[...], 0, 1)               # (BN, 1)
        dl = ((p - hit) * g_col).astype(w.dtype)             # (BN, BV)
        dxc = jax.lax.dot_general(
            dl, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (BN, D)
        # the transposed product as a contraction over dim 0 of both:
        # Mosaic's transpose of the bf16 dl tile hides under the matmuls
        dwc = jax.lax.dot_general(
            dl, x_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (BV, D)

        if nv == 1:
            dx_ref[...] = dxc.astype(dx_ref.dtype)
        else:
            (dx_scr,) = scratch

            @pl.when(iv == 0)
            def _():
                dx_scr[...] = dxc

            @pl.when(iv > 0)
            def _():
                dx_scr[...] += dxc

            @pl.when(iv == nv - 1)
            def _():
                dx_ref[...] = dx_scr[...].astype(dx_ref.dtype)

        if nn == 1:
            dw_ref[...] = dwc.astype(dw_ref.dtype)
        elif nv == 1:
            @pl.when(i_n == 0)
            def _():
                dw_ref[...] = dwc

            @pl.when(i_n > 0)
            def _():
                dw_ref[...] += dwc
        else:
            def accumulate(stage, read, write):
                # the buffers are as wide as the HBM accumulator, whose
                # rows are padded up to whole lane tiles
                @pl.when(i_n == 0)
                def _():
                    stage[:, :dwc.shape[1]] = dwc

                @pl.when(i_n > 0)
                def _():
                    read().wait()
                    stage[:, :dwc.shape[1]] += dwc

                write().start()

            on_stage(accumulate)

    _mask_last_tile(tile, iv, nv, v_total % block_v)

    if by_hand:
        @pl.when(step == nn * nv - 1)
        def _():
            on_stage(lambda stage, read, write: write().wait())
            on_stage(lambda stage, read, write: write().wait(), step - 1)


def _bwd_call(x2d, w, lbl_row, g_row, lse_row, bn, bv, v_total, interpret):
    """(dx, dw) at the padded shapes. dx: x2d's dtype; dw: w's dtype."""
    n, d = x2d.shape
    vp = w.shape[0]
    nn, nv = n // bn, vp // bv
    xspec = pl.BlockSpec((bn, d), lambda i, j: (i, 0))
    wspec = pl.BlockSpec((bv, d), lambda i, j: (j, 0))
    rspec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    # dW: cast in the kernel where one token block is all there is, else
    # an f32 accumulator: a resident block (one vocab tile), or in HBM
    # with rows of whole lane tiles, which is all Mosaic copies by hand
    dw_spec = wspec
    dw_shape = jax.ShapeDtypeStruct(
        (vp, d), w.dtype if nn == 1 else jnp.float32)
    scratch = [pltpu.VMEM((bn, d), jnp.float32)] if nv > 1 else []
    if nn > 1 and nv > 1:
        dp = _round_up(d, 128)
        dw_spec = pl.BlockSpec(memory_space=pl.ANY)
        dw_shape = jax.ShapeDtypeStruct((vp, dp), jnp.float32)
        scratch += [pltpu.VMEM((bv, dp), jnp.float32)] * 2
        scratch += [pltpu.SemaphoreType.DMA((2,))] * 2
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=bv, v_total=v_total,
                          nn=nn, nv=nv),
        grid=(nn, nv),
        in_specs=[xspec, wspec, rspec, rspec, rspec],
        out_specs=[xspec, dw_spec],
        out_shape=[jax.ShapeDtypeStruct((n, d), x2d.dtype), dw_shape],
        scratch_shapes=scratch,
        compiler_params=compiler_params(("arbitrary", "arbitrary")),
        interpret=interpret,
        name="lmhead_ce_dw",
        **_cost_kwargs(6 * n * vp * d,
                       2 * x2d.nbytes + nn * (w.nbytes + 8 * vp * d),
                       transcendentals=n * vp),
    )(x2d, w, lbl_row, g_row, lse_row)
    return dx, dw[:, :d].astype(w.dtype)


# ---------------------------------------------------------------- custom vjp


def _blocks(x2d, w, block_n, block_v):
    """The call's tiles: by shape (``tiles``), or the explicit override
    clamped to the call."""
    (n, d), v = x2d.shape, w.shape[0]
    bn, bv = tiles(n, d, v, x2d.dtype.itemsize)
    if block_n:
        bn = min(int(block_n), _round_up(max(n, 1), 8))
    if block_v:
        bv = min(int(block_v), _round_up(v, 128))
    return bn, bv


def _pad_tokens(x2d, lbl, bn):
    n = x2d.shape[0]
    np_ = _round_up(n, bn)
    if np_ != n:
        x2d = jnp.pad(x2d, ((0, np_ - n), (0, 0)))
        lbl = jnp.pad(lbl, (0, np_ - n))
    return x2d, lbl, n


def _pad_vocab(w, bv):
    v = w.shape[0]
    vp = _round_up(v, bv)
    if vp != v:
        w = jnp.pad(w, ((0, vp - v), (0, 0)))
    return w, v


def _shift_labels(lbl, w, axis_name):
    """Labels into the local shard's column space: per-shard columns are
    numbered 0..V_local-1, so out-of-shard labels match no column and
    contribute exactly 0 to picked / d_logits."""
    if not axis_name:
        return lbl
    off = (jax.lax.axis_index(axis_name) * w.shape[0]).astype(jnp.int32)
    return lbl - off


def _run_fwd(x2d, w, lbl, axis_name, block_n, block_v, interpret):
    """Padded forward sweep (+ cross-shard combine): (nll, lse), both at
    the caller's unpadded token count."""
    bn, bv = _blocks(x2d, w, block_n, block_v)
    lbl = _shift_labels(lbl.astype(jnp.int32), w, axis_name)
    pk = _picked(x2d, w, lbl)
    xp, _, n = _pad_tokens(x2d, lbl, bn)
    wp, v_real = _pad_vocab(w, bv)
    m, l = _stats_call(xp, wp, bn, bv, v_real, interpret)
    m, l = m[:n], l[:n]
    if axis_name:
        # combine the per-shard partial stats across the vocab (tp)
        # axis: one pmax for the running max, one psum for the (rescaled
        # sum-exp, picked) pair — the collective the recipe's analytic
        # plan prices as the lmhead_ce_fused term
        mg = jax.lax.pmax(m, axis_name)
        lp = jax.lax.psum(jnp.stack([l * jnp.exp(m - mg), pk]), axis_name)
        l, pk = lp[0], lp[1]
        m = mg
    lse = m + jnp.log(jnp.where(l > 0.0, l, 1.0))
    return lse - pk, lse


def _run_bwd(x2d, w, lbl, lse, g, axis_name, block_n, block_v, interpret):
    """Padded backward kernel: (dx, dw) with dx at the caller's token
    count and dw covering the local (unpadded) vocab rows. No
    collectives here — the caller owns every cross-shard reduction."""
    bn, bv = _blocks(x2d, w, block_n, block_v)
    lbl = _shift_labels(lbl.astype(jnp.int32), w, axis_name)
    xp, lblp, n = _pad_tokens(x2d, lbl, bn)
    wp, v_real = _pad_vocab(w, bv)
    np_ = xp.shape[0]
    # padded rows carry zero cotangent, so their (arbitrary) lse and the
    # all-zero x rows contribute nothing to either gradient
    g_row = jnp.pad(g.astype(jnp.float32), (0, np_ - n))[None, :]
    lse_row = jnp.pad(lse, (0, np_ - n))[None, :]
    dx, dw = _bwd_call(xp, wp, lblp[None, :], g_row, lse_row, bn, bv,
                       v_real, interpret)
    return dx[:n], dw[:v_real]


# -- single-device (or single-shard) entry ----------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ce_local(x2d, w, lbl, block_n, block_v, interpret):
    nll, _ = _ce_local_fwd(x2d, w, lbl, block_n, block_v, interpret)
    return nll


def _ce_local_fwd(x2d, w, lbl, block_n, block_v, interpret):
    nll, lse = _run_fwd(x2d, w, lbl, None, block_n, block_v, interpret)
    return nll, (x2d, w, lbl, lse)


def _ce_local_bwd(block_n, block_v, interpret, res, g):
    x2d, w, lbl, lse = res
    dx, dw = _run_bwd(x2d, w, lbl, lse, g, None, block_n, block_v,
                      interpret)
    return dx, dw, None


_ce_local.defvjp(_ce_local_fwd, _ce_local_bwd)


def lmhead_ce(x2d, w, labels, block_n: Optional[int] = None,
              block_v: Optional[int] = None,
              interpret: Optional[bool] = None):
    """Per-token NLL of ``softmax(x2d @ w^T)`` at ``labels`` without ever
    materializing the [tokens, vocab] logits. x2d: (N, D); w: (V, D)
    (the tied-embedding layout); labels: (N,) int. Differentiable in
    x2d and w (flash-style rematerializing backward); token count and
    vocab may be arbitrary (padded up to tile multiples internally)."""
    if interpret is None:
        interpret = not on_tpu()
    return _ce_local(x2d, w, labels, block_n, block_v, bool(interpret))


# -- mesh entry (manual SPMD region inside a GSPMD program) -----------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ce_sharded(x2d, w, lbl, cfg):
    nll, _ = _ce_sharded_fwd(x2d, w, lbl, cfg)
    return nll


def _ce_sharded_specs(cfg):
    from jax.sharding import PartitionSpec as P

    (mesh, batch_axes, vocab_axis, gather_axis, *_rest) = cfg
    bspec = (batch_axes if len(batch_axes) > 1 else batch_axes[0]) \
        if batch_axes else None
    xspec = P(bspec, None)
    lspec = P(bspec)
    if vocab_axis:
        wspec = P(vocab_axis, None)
    elif gather_axis:
        wspec = P(gather_axis, None)
    else:
        wspec = P(None, None)
    return xspec, wspec, lspec


def _ce_sharded_fwd(x2d, w, lbl, cfg):
    (mesh, batch_axes, vocab_axis, gather_axis, block_n, block_v,
     interpret) = cfg
    xspec, wspec, lspec = _ce_sharded_specs(cfg)

    def inner(xl, wl, ll):
        if gather_axis:
            wl = jax.lax.all_gather(wl, gather_axis, axis=0, tiled=True)
        return _run_fwd(xl, wl, ll, vocab_axis, block_n, block_v,
                        interpret)

    nll, lse = shard_map(
        inner, mesh=mesh, in_specs=(xspec, wspec, lspec),
        out_specs=(lspec, lspec), check_vma=False,
    )(x2d, w, lbl)
    return nll, (x2d, w, lbl, lse)


def _ce_sharded_bwd(cfg, res, g):
    """Both shard_map regions carry EXPLICIT collectives with exact
    out_specs — nothing is left to shard_map's transpose machinery
    (check_vma is off for the pallas calls, under which the
    transpose of replicated-input cotangents is not trustworthy)."""
    (mesh, batch_axes, vocab_axis, gather_axis, block_n, block_v,
     interpret) = cfg
    x2d, w, lbl, lse = res
    xspec, wspec, lspec = _ce_sharded_specs(cfg)

    def inner(xl, wl, ll, gl, lsel):
        wl_use = wl
        if gather_axis:
            wl_use = jax.lax.all_gather(wl, gather_axis, axis=0,
                                        tiled=True)
        dx, dw = _run_bwd(xl, wl_use, ll, lsel, gl, vocab_axis, block_n,
                          block_v, interpret)
        if vocab_axis:
            # each shard's dx covers only its vocab slice of the sum
            dx = jax.lax.psum(dx, vocab_axis)
        # dw covers only this shard's token rows; sum the batch axes,
        # folding the gather axis's sum into the reduce-scatter that
        # also restores the weight's shard layout
        reduce_axes = tuple(a for a in batch_axes if a != gather_axis)
        if reduce_axes:
            dw = jax.lax.psum(dw, reduce_axes)
        if gather_axis:
            dw = jax.lax.psum_scatter(dw, gather_axis,
                                      scatter_dimension=0, tiled=True)
        return dx, dw

    dx, dw = shard_map(
        inner, mesh=mesh, in_specs=(xspec, wspec, lspec, lspec, lspec),
        out_specs=(xspec, wspec), check_vma=False,
    )(x2d, w, lbl, g, lse)
    return dx, dw, None


_ce_sharded.defvjp(_ce_sharded_fwd, _ce_sharded_bwd)


def lmhead_ce_sharded(x2d, w, labels, mesh,
                      batch_axes: Sequence[str] = (),
                      vocab_axis: Optional[str] = None,
                      gather_axis: Optional[str] = None,
                      block_n: Optional[int] = None,
                      block_v: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """The mesh-program composition: run the fused CE as a manual-SPMD
    region inside the surrounding GSPMD program (GSPMD cannot partition
    a custom call — without this region it would all-gather the operands
    and run the kernel replicated, destroying the sharding's point).

    - ``batch_axes``: mesh axes the token rows shard over (dp/fsdp) —
      embarrassingly parallel; dw sums them on the way out;
    - ``vocab_axis``: axis the weight's vocab dim shards over (tp) —
      partial (max, sum-exp, picked) stats combine with one pmax + one
      psum, the backward psums the partial dx, dW stays shard-local;
    - ``gather_axis``: fsdp-style vocab-dim-sharded weight gathered at
      use (the 2x param-gather bytes the analytic plan already prices);
      the backward's reduce-scatter returns dW to the shard layout.
    """
    if interpret is None:
        interpret = not on_tpu()
    cfg = (mesh, tuple(a for a in batch_axes if a),
           vocab_axis or None, gather_axis or None,
           block_n, block_v, bool(interpret))
    return _ce_sharded(x2d, w, labels.astype(jnp.int32), cfg)
