"""Ring attention: context/sequence parallelism over an ICI ring.

Green-field for this framework (SURVEY.md §5.7: the reference has no
ring/context parallelism — its long-sequence story is LoD ragged tensors
and pipeline microbatching). Design follows the blockwise-attention ring
schedule (Liu et al., Ring Attention): the sequence axis is sharded over a
mesh axis; each device keeps its Q shard resident and streams K/V shards
around the ring with `lax.ppermute`, merging per-block partial attention
with the online-softmax (running max / sum) recurrence, so the full T x T
score matrix never materializes on one chip and comm overlaps compute.

Causal masking operates on *global* positions: rank r holds query rows
[r*Tq, (r+1)*Tq); the k-th ring step brings the K/V shard of rank
(r - k) mod n, giving each score block an offset-dependent mask.

Exposed as `ring_attention(q, k, v, mesh, seq_axis=...)` (a shard_map
region composable inside the GSPMD-jit executor) and as the
`ring_attention_tpu` op for program-level use.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, bias_mask, scale):
    """One Q-shard x K-shard block: returns (unnormalized out, row max,
    row sumexp) for online-softmax merging. q,k,v: [B,H,T,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias_mask is not None:
        s = jnp.where(bias_mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    # rows fully masked (causal first blocks) produce -inf max; guard exp
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two partial softmax accumulators (flash-attention recurrence)."""
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, scale: float):
    """Per-shard body (runs inside shard_map). q,k,v: [B,H,Tq,D] local."""
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    tq = q.shape[2]
    tk = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]  # send k/v to next rank

    q_pos = rank * tq + jnp.arange(tq)  # global query rows

    def block(i, k_blk, v_blk, o, m, l):
        src = (rank - i) % n  # whose K/V shard we hold at step i
        if causal:
            k_pos = src * tk + jnp.arange(tk)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]  # [1,1,Tq,Tk]
        else:
            mask = None
        bo, bm, bl = _block_attn(q, k_blk, v_blk, mask, scale)
        return _merge(o, m, l, bo, bm, bl)

    # step 0 is peeled so the loop permutes *before* each block — the
    # final iteration's K/V then stay put instead of making a wasted
    # shard-sized ICI round-trip after the last block
    o0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    m0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    o, m, l = block(0, k, v, o0, m0, l0)

    def step(i, carry):
        k_blk, v_blk, o, m, l = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        o, m, l = block(i, k_blk, v_blk, o, m, l)
        return k_blk, v_blk, o, m, l

    # static trip count → reverse-differentiable
    _, _, o, m, l = jax.lax.fori_loop(1, n, step, (k, v, o, m, l))
    l_safe = jnp.where(l > 0, l, 1.0)
    return (o / l_safe[..., None]).astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    seq_axis: str = "sp",
    batch_axis: Optional[str] = "dp",
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Global-view entry: q,k,v are [B,H,T,D] arrays (sharded or not);
    the sequence dim is sharded over `seq_axis` and attention runs as a
    shard_map ring. Composable under jit: the surrounding program stays
    GSPMD-partitioned while this region is manual SPMD."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b_ax = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(b_ax, None, seq_axis, None)

    fn = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal, scale=scale
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
