"""Fused lm-head + softmax cross-entropy as pallas TPU kernels.

The raw-speed round's tentpole (ROADMAP item 2): OPBENCH_r05 shows the
two ops that dwarf the GPT step are ``matmul_lmhead`` (~6.4ms) and
``softmax_with_cross_entropy`` (~3.1ms) — and most of the CE cost is not
compute but the [tokens, vocab] logits tensor's HBM round-trip (bf16
logits at B*T=16384, V=32768 are 1GB written by the matmul and read
straight back by the softmax, twice more in the backward). The chunked
``fused_lm_head_ce`` lax-loop (ops/fused_ops.py) already avoids holding
every chunk at once but still materializes one [C, V] tile per step of a
*sequential* scan — the MXU stalls on every chunk's HBM traffic.

Here the whole loss is one flash-style kernel family:

- forward: a blocked online-softmax sweep over vocab tiles. For each
  token block the kernel walks the vocab tiles, keeps running
  (max, sum-exp, picked-logit) accumulators in VMEM, and writes only
  three f32 row stats per token — the (block_n, block_v) logits tile
  lives in VMEM only, *never* in HBM;
- backward (custom VJP): two kernels rematerialize the logits tile
  blockwise from the saved per-row logsumexp (exactly the flash
  backward pattern in flash_attention.py): the dx pass keeps a
  (block_n, D) accumulator and sweeps vocab tiles; the dw pass keeps a
  (block_v, D) accumulator and sweeps token blocks. ``dW``/``dx`` are
  accumulated in f32 and cast once at the end.

Memory math (the README "Raw speed" section walks this): the naive path
holds tokens*vocab logits (+ the same again as the backward's d_logits);
the pallas path holds 3*tokens f32 of row stats — at the bench shapes
that is 1GB+ vs 192KB, and the AOT ``memory_analysis`` peak of the
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
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import shard_map

from .backend import compiler_params, on_tpu

_NEG_INF = -1e30  # finite stand-in for -inf (inf-inf = nan in rescaling)

# default tiles: (256, 512) keeps the fwd working set (x tile 384KB +
# w tile 768KB + f32 score tile 512KB + stats) and the dw pass's
# (block_v, D) f32 accumulator comfortably inside the 16MB scoped-vmem
# budget at D=768 while feeding the MXU full 128-lane tiles
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 512


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _cost_kwargs(flops: int, bytes_accessed: int, transcendentals: int = 0):
    """Analytic pl.CostEstimate for the kernel: XLA's cost_analysis
    cannot see inside a custom call, so the kernel states its own FLOPs
    — what keeps achieved-MFU attribution (tools/xla_report.py) from
    reporting the lm-head as vanished compute."""
    return {"cost_estimate": pl.CostEstimate(
        flops=int(flops), transcendentals=int(transcendentals),
        bytes_accessed=int(bytes_accessed))}


# ---------------------------------------------------------------- forward


def _stats_kernel(x_ref, w_ref, lbl_ref, m_ref, l_ref, pk_ref,
                  m_scr, l_scr, pk_scr, *, block_v, v_total):
    """One token block x one vocab tile: online (max, sum-exp, picked)
    update. Row stats live one lane each in (block_n, 128) VMEM scratch
    (the flash_attention row-stat convention); outputs are (1, block_n)
    row vectors written at the last vocab tile."""
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        pk_scr[:] = jnp.zeros_like(pk_scr)

    x = x_ref[...]                       # (BN, D)
    w = w_ref[...]                       # (BV, D)
    s = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                    # (BN, BV) — VMEM only, never HBM
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    lbl = lbl_ref[0]                     # (BN,) int32
    hit = col == lbl[:, None]
    if v_total % block_v:
        # vocab padded up to a tile multiple: padded columns must not
        # contribute to the softmax stats — NOR to picked (an
        # out-of-shard label under tp can numerically land inside the
        # padded range and must not pick up the mask value)
        s = jnp.where(col < v_total, s, _NEG_INF)
        hit = hit & (col < v_total)
    pk_scr[:, :1] += jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(jnp.exp(s - m_new), axis=-1,
                                           keepdims=True)
    m_scr[:, :1] = m_new
    l_scr[:, :1] = l_new

    @pl.when(iv == nv - 1)
    def _finish():
        m_ref[...] = jnp.swapaxes(m_scr[:, :1], 0, 1)     # (1, BN)
        l_ref[...] = jnp.swapaxes(l_scr[:, :1], 0, 1)
        pk_ref[...] = jnp.swapaxes(pk_scr[:, :1], 0, 1)


def _specs(bn, bv, d, swap_grid=False):
    """(x tile, w tile, row-stat tile) BlockSpecs. The forward/dx grid is
    (n-blocks, v-tiles); swap_grid flips it for the dw pass (v-tiles in
    parallel, token blocks sequential)."""
    if swap_grid:
        ni = lambda iv, i_n: i_n
        vi = lambda iv, i_n: iv
    else:
        ni = lambda i_n, iv: i_n
        vi = lambda i_n, iv: iv
    xspec = pl.BlockSpec((bn, d), lambda i, j: (ni(i, j), 0))
    wspec = pl.BlockSpec((bv, d), lambda i, j: (vi(i, j), 0))
    rspec = pl.BlockSpec((1, bn), lambda i, j: (0, ni(i, j)))
    return xspec, wspec, rspec


def _stats_call(x2d, w, lbl_row, block_n, block_v, v_total, interpret):
    n, d = x2d.shape
    vp = w.shape[0]
    bn, bv = min(block_n, n), min(block_v, vp)
    grid = (n // bn, vp // bv)
    xspec, wspec, rspec = _specs(bn, bv, d)
    stat = jax.ShapeDtypeStruct((1, n), jnp.float32)
    m, l, pk = pl.pallas_call(
        functools.partial(_stats_kernel, block_v=bv, v_total=v_total),
        grid=grid,
        in_specs=[xspec, wspec, rspec],
        out_specs=[rspec, rspec, rspec],
        out_shape=[stat, stat, stat],
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32)] * 3,
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="lmhead_ce_stats",
        **_cost_kwargs(2 * n * vp * d,
                       x2d.nbytes + w.nbytes + 3 * 4 * n,
                       transcendentals=n * vp),
    )(x2d, w, lbl_row)
    return m[0], l[0], pk[0]


# ---------------------------------------------------------------- backward


def _dx_kernel(x_ref, w_ref, lbl_ref, g_ref, lse_ref, dx_ref, dx_scr,
               *, block_v, v_total):
    """dx = (softmax - onehot) * g @ W, vocab tiles rematerialized from
    the saved per-row logsumexp; (BN, D) f32 accumulator across the
    vocab sweep."""
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_total % block_v:
        s = jnp.where(col < v_total, s, _NEG_INF)
    lse_col = jnp.swapaxes(lse_ref[...], 0, 1)           # (BN, 1)
    p = jnp.exp(s - lse_col)
    hit = (col == lbl_ref[0][:, None]).astype(jnp.float32)
    g_col = jnp.swapaxes(g_ref[...], 0, 1)               # (BN, 1)
    dl = ((p - hit) * g_col).astype(w.dtype)             # (BN, BV) bf16
    dx_scr[:] += jax.lax.dot_general(
        dl, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == nv - 1)
    def _finish():
        dx_ref[...] = dx_scr[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, lbl_ref, g_ref, lse_ref, dw_ref, dw_scr,
               *, block_v, v_total):
    """dW = ((softmax - onehot) * g)^T @ X. k-major orientation (the
    flash dkv trick): the score tile is built transposed as (BV, BN) so
    every product is a standard (M,K)x(K,N) matmul, and the (1, BN) row
    stats broadcast over the vocab rows with no transpose."""
    iv, i_n = pl.program_id(0), pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(i_n == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[...]                       # (BN, D)
    w = w_ref[...]                       # (BV, D)
    st = jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                    # (BV, BN)
    colr = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    if v_total % block_v:
        st = jnp.where(colr < v_total, st, _NEG_INF)
    pt = jnp.exp(st - lse_ref[...])      # (1, BN) broadcasts over rows
    hit_t = (colr == lbl_ref[...]).astype(jnp.float32)
    dlt = ((pt - hit_t) * g_ref[...]).astype(x.dtype)    # (BV, BN)
    dw_scr[:] += jax.lax.dot_general(
        dlt, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i_n == nn - 1)
    def _finish():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def _dx_call(x2d, w, lbl_row, g_row, lse_row, block_n, block_v, v_total,
             interpret):
    n, d = x2d.shape
    vp = w.shape[0]
    bn, bv = min(block_n, n), min(block_v, vp)
    xspec, wspec, rspec = _specs(bn, bv, d)
    return pl.pallas_call(
        functools.partial(_dx_kernel, block_v=bv, v_total=v_total),
        grid=(n // bn, vp // bv),
        in_specs=[xspec, wspec, rspec, rspec, rspec],
        out_specs=[xspec],
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)],
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="lmhead_ce_dx",
        **_cost_kwargs(4 * n * vp * d, 2 * x2d.nbytes + w.nbytes,
                       transcendentals=n * vp),
    )(x2d, w, lbl_row, g_row, lse_row)[0]


def _dw_call(x2d, w, lbl_row, g_row, lse_row, block_n, block_v, v_total,
             interpret):
    n, d = x2d.shape
    vp = w.shape[0]
    bn, bv = min(block_n, n), min(block_v, vp)
    xspec, wspec, rspec = _specs(bn, bv, d, swap_grid=True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, block_v=bv, v_total=v_total),
        grid=(vp // bv, n // bn),
        in_specs=[xspec, wspec, rspec, rspec, rspec],
        out_specs=[wspec],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype)],
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="lmhead_ce_dw",
        **_cost_kwargs(4 * n * vp * d, x2d.nbytes + 2 * w.nbytes,
                       transcendentals=n * vp),
    )(x2d, w, lbl_row, g_row, lse_row)[0]


# ---------------------------------------------------------------- custom vjp


def _clamp_blocks(n: int, v: int, block_n: int, block_v: int):
    bn = min(int(block_n), _round_up(max(n, 1), 8))
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
    n, _ = x2d.shape
    bn, bv = _clamp_blocks(n, w.shape[0], block_n, block_v)
    lbl = _shift_labels(lbl.astype(jnp.int32), w, axis_name)
    xp, lblp, n = _pad_tokens(x2d, lbl, bn)
    wp, v_real = _pad_vocab(w, bv)
    m, l, pk = _stats_call(xp, wp, lblp[None, :], bn, bv, v_real, interpret)
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
    return (lse - pk)[:n], lse[:n]


def _run_bwd(x2d, w, lbl, lse, g, axis_name, block_n, block_v, interpret):
    """Padded backward kernels: (dx, dw) with dx at the caller's token
    count and dw covering the local (unpadded) vocab rows. No
    collectives here — the caller owns every cross-shard reduction."""
    n, _ = x2d.shape
    bn, bv = _clamp_blocks(n, w.shape[0], block_n, block_v)
    lbl = _shift_labels(lbl.astype(jnp.int32), w, axis_name)
    xp, lblp, n = _pad_tokens(x2d, lbl, bn)
    wp, v_real = _pad_vocab(w, bv)
    np_ = xp.shape[0]
    # padded rows carry zero cotangent, so their (arbitrary) lse and the
    # all-zero x rows contribute nothing to either gradient
    g_row = jnp.pad(g.astype(jnp.float32), (0, np_ - n))[None, :]
    lse_row = jnp.pad(lse, (0, np_ - n))[None, :]
    dx = _dx_call(xp, wp, lblp[None, :], g_row, lse_row, bn, bv, v_real,
                  interpret)
    dw = _dw_call(xp, wp, lblp[None, :], g_row, lse_row, bn, bv, v_real,
                  interpret)
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


def lmhead_ce(x2d, w, labels, block_n: int = DEFAULT_BLOCK_N,
              block_v: int = DEFAULT_BLOCK_V,
              interpret: Optional[bool] = None):
    """Per-token NLL of ``softmax(x2d @ w^T)`` at ``labels`` without ever
    materializing the [tokens, vocab] logits. x2d: (N, D); w: (V, D)
    (the tied-embedding layout); labels: (N,) int. Differentiable in
    x2d and w (flash-style rematerializing backward); token count and
    vocab may be arbitrary (padded up to tile multiples internally)."""
    if interpret is None:
        interpret = not on_tpu()
    return _ce_local(x2d, w, labels, int(block_n), int(block_v),
                     bool(interpret))


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
                      block_n: int = DEFAULT_BLOCK_N,
                      block_v: int = DEFAULT_BLOCK_V,
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
           int(block_n), int(block_v), bool(interpret))
    return _ce_sharded(x2d, w, labels.astype(jnp.int32), cfg)
