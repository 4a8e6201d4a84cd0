"""Decode attention over the paged KV pool as it lies: a pallas TPU kernel.

One query token per batch slot against that slot's own context, read
straight out of the serving pool ``[n_layer * n_blocks, block_size, row]``
(serving/kv_cache.py). A position's row is one of two things: per K|V
head, that head's K then its V (``n_kv_head * 2 * head_dim`` lanes:
:func:`paged_attention`), or ONE latent row that every head shares, whose
leading lanes are also its V (:func:`paged_latent_attention`, further
down). The XLA formulation it replaces gathers every slot's whole window
(``max_seq_len`` positions) into a temporary and scores all of it; this
kernel walks only the pages a slot's context occupies, ``0 ..
context_len // block_size``, so a tick moves the live K and V once and
nothing else.

Schedule: the grid is the batch, walked in order. A step copies its
slot's pages HBM -> VMEM itself (the pool has no BlockSpec: nothing is
staged or relaid), ``pages_per_step`` pages at a time into one of two
buffers, and starts the next copy (the slot's next pages, or the NEXT
slot's first ones) before it computes on the current buffer. Running
maximum, sum and weighted rows per head live in VMEM scratch (online
softmax, float32). How long a step is, and how its copies are started
and waited for, follows from the row's bytes (:func:`step_schedule`): a
step's fixed work is hidden by the step's own bytes or not at all.

All heads at once, no lane slicing: a head is ``2 * head_dim`` lanes of
the row (a multiple of 128), its K half then its V half. q arrives
zero-padded over the V halves and becomes a block-diagonal ``[heads,
row]`` matrix, so ONE matmul against the whole row scores every head
(the V lanes meet zeros), and ``p @ rows`` weighs every head's lanes in
ONE more; a head's own output is the diagonal block of that product, and
the caller takes its V half at the very end. The matrix unit is bound by
the K/V tiles it has to load either way, so the off-diagonal products
cost nothing that matters.

Grouped queries: a row of ``n_kv`` K|V heads under ``n_kv * group`` query
heads, query head ``i`` over K|V head ``i // group``. The caller hands q
group-major, ``[group, row]``: line ``r`` holds, over the K lanes of K|V
head ``j``, query head ``j * group + r``. Matrix row ``r * n_kv + j`` is
that head, its diagonal block the lanes of K|V head ``j``, so each line
of the output is again a sum over ``n_kv`` aligned rows whose blocks do
not overlap. With ``group == 1`` this is the kernel above, op for op.

The latent mode (latent attention with the up-projections absorbed into q
and into the output): the row is ``[latent | rotated key lanes | zeros]``,
every head's K is the whole row and its V the row's leading ``v_lanes``.
That is the same two matmuls with nothing to mask: q arrives ``[heads,
row]`` (absorbed q over the latent lanes, rotated q over the rotated ones,
zeros over the padding), ``q @ rows^T`` scores every head, and ``p @
rows[:, :v_lanes]`` weighs the row's V prefix for every head: the output.
Same page walk, double buffer and online softmax; the kernel is named
``paged_latent_attention`` so that a trace tells the two apart.
All heads share the bytes of one row (``2 * heads * (row + v_lanes)``
operations against ``row * itemsize`` bytes a position), and on the chip
neither paces it: at 128 positions a step the arithmetic alone took 70 % of
the kernel's time and the copies alone 44 %, the two in turn and not
beside each other, the arithmetic waiting on the matrix unit's round trip
for 64 streamed rows and the copies on their own scalar work (PERF.md,
PR 51). Longer steps and batched copies halved both.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params, on_tpu

_NEG_INF = -1e30  # finite: a masked score never makes inf - inf
_LANES = 128
_STEP_TOKENS = 128  # the shortest step: one full contraction of p @ rows
_LONG_STEP = 512  # the longest: past it a context's last, part empty step
# costs more than a step's fixed work saves (PERF.md, PR 51)
_BUFFER_BYTES = 1 << 20  # what one page buffer may hold
_GROUP = 4  # page copies started in one loop body of a batched step


class Step(NamedTuple):
    """How the kernel walks a slot's pages (:func:`step_schedule`)."""
    positions: int  # positions a step copies, scores and weighs
    buffers: int  # page buffers of that many positions; one step's copies
    # are in flight while the step before them is computed
    batched: bool  # a step's copies are started _GROUP to a loop body and
    # waited for by their bytes (a wait for each power of two in its
    # pages), not one page a loop body both times


def step_schedule(row_bytes: int) -> Step:
    """The step of a pool whose position is ``row_bytes`` wide: what the
    kernel can see of its own call, and nothing a caller sets.

    A step has fixed work that only its own bytes hide: its scalar
    bookkeeping, one pass over the statistics and one rescale of the
    accumulator, and per page a descriptor (~30 dependent scalar
    operations, bounds checks among them) and a wait, none of which a
    matmul overlaps. A row of 6-8 KB carries 0.8-1 MB in 128 positions and
    sits on its bytes: it keeps 128 positions and a wait a page. A narrower
    row takes twice the positions for as long as a buffer stays under
    ``_BUFFER_BYTES`` (512 positions at most), so that its step carries
    what a wide row's does, and a step longer than the shortest batches its
    copies. Both modes alike: at a 1,280 B latent row and a 2,048 B
    grouped-query row the same schedule won the sweep (PERF.md, PR 51)."""
    positions = _STEP_TOKENS
    while (positions < _LONG_STEP
           and 2 * positions * row_bytes <= _BUFFER_BYTES):
        positions *= 2
    return Step(positions, 2, positions > _STEP_TOKENS)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of one native tile of ``dtype`` (8 of 32 bits, 16 of 16)."""
    return 32 // jnp.dtype(dtype).itemsize


def unsupported(head_dim: int, block_size: int, dtype,
                n_head: int = 1, n_kv_head: int = 1, latent=None) -> str:
    """Why a pool of this geometry cannot take the kernel ('' if it
    can): the kernel copies whole pages and multiplies whole rows, so a
    head has to fill whole 128-lane tiles and a page whole sublane tiles
    (a row or a page the runtime would pad is not what the copies
    assume). Grouped queries (``n_head`` over fewer ``n_kv_head``) sum
    each output line over ``n_kv_head`` rows of the float32 accumulator,
    which have to be whole 8-row tiles. ``latent = (row_lanes, v_lanes)``
    asks for the latent mode instead (``head_dim`` and the head counts
    say nothing then): the shared row and its V prefix have to be whole
    128-lane tiles."""
    if latent is not None:
        row_lanes, v_lanes = latent
        if row_lanes % _LANES or v_lanes % _LANES or not (
                0 < v_lanes <= row_lanes):
            return (f"a latent row of {row_lanes} lanes whose leading "
                    f"{v_lanes} are V: not whole {_LANES}-lane tiles")
    else:
        if n_head != n_kv_head and (n_head % n_kv_head or n_kv_head % 8):
            return (f"{n_head} query heads over {n_kv_head} K|V heads: not "
                    f"a whole group over whole 8-row tiles")
        if (2 * head_dim) % _LANES:
            return (f"a head's K|V is {2 * head_dim} lanes, not a multiple "
                    f"of {_LANES}")
        row_lanes = n_kv_head * 2 * head_dim
    step = step_schedule(row_lanes * jnp.dtype(dtype).itemsize).positions
    if block_size % _sublanes(dtype) or step % block_size:
        return (f"a page of {block_size} tokens is not a whole number of "
                f"{_sublanes(dtype)}-row tiles dividing {step}")
    return ""


def vmem_scratch_bytes(n_head: int, head_dim: int, block_size: int,
                       dtype, n_kv_head: int = 0,
                       latent: tuple = ()) -> int:
    """VMEM the kernel's scratch takes (the page buffers of its step, the
    accumulator, the two statistics), for the compile report. ``latent =
    (row_lanes, v_lanes)``: the latent mode's shared row and its V prefix,
    which is all its accumulator holds."""
    hp = _round_up(n_head, _sublanes(dtype))
    hw, acc = latent or ((n_kv_head or n_head) * 2 * head_dim,) * 2
    itemsize = jnp.dtype(dtype).itemsize
    step = step_schedule(hw * itemsize)
    return (step.buffers * step.positions * hw * itemsize
            + hp * acc * 4 + 2 * hp * _LANES * 4)


def _kernel(tables_ref, lens_ref, q_ref, pool_ref, o_ref,
            buf, sem, cur, m_scr, l_scr, acc_scr,
            *, block_size, pages_per_step, max_blocks, head_lanes, scale,
            n_kv=0, group=1, v_lanes=0, batched=False):
    b, nb = pl.program_id(0), pl.num_programs(0)
    bs, pps, w = block_size, pages_per_step, head_lanes
    step_tokens = pps * bs
    hp, hw = acc_scr.shape[0], buf.shape[2]

    def n_pages(i):
        return jnp.minimum(lens_ref[i] // bs + 1, max_blocks)

    def page_copy(i, first, j, slot):
        """The copy of page ``first + j`` of slot ``i``'s context to its
        place ``j`` in buffer ``slot``."""
        return pltpu.make_async_copy(
            pool_ref.at[tables_ref[i * max_blocks + first + j]],
            buf.at[slot, pl.ds(pl.multiple_of(j * bs, bs), bs)],
            sem.at[slot])

    def copies(i, c, slot, act, first_page=0):
        """``act`` on the copy of each page slot ``i`` has in step ``c``,
        from its ``first_page`` on (pages past the context are neither
        started nor waited for)."""
        first = c * pps

        def one(j, carry):
            act(page_copy(i, first, j, slot))
            return carry

        jax.lax.fori_loop(first_page,
                          jnp.minimum(pps, n_pages(i) - first), one, 0)

    def start(i, c, slot):
        """Starts the copies of step ``c`` of slot ``i``."""
        if not batched:
            copies(i, c, slot, lambda dma: dma.start())
            return
        # a copy's descriptor is a chain of ~30 dependent scalar operations
        # (the table entry, two addresses, two bounds checks): _GROUP of
        # them to a loop body let the scheduler overlap the chains
        first = c * pps
        groups = jnp.minimum(pps, n_pages(i) - first) // _GROUP

        def group(g, carry):
            for u in range(_GROUP):
                page_copy(i, first, g * _GROUP + u, slot).start()
            return carry

        jax.lax.fori_loop(0, groups, group, 0)
        copies(i, c, slot, lambda dma: dma.start(), groups * _GROUP)

    def wait(i, c, slot):
        """Until the copies of step ``c`` of slot ``i`` have landed."""
        if not batched:
            copies(i, c, slot, lambda dma: dma.wait())
            return
        # a copy's semaphore counts bytes, so ONE wait for as many rows as
        # several pages hold stands for all of them: a wait for each power
        # of two in the step's pages, whichever rows it names
        n = jnp.minimum(pps, n_pages(i) - c * pps)
        for k in (1 << j for j in range(pps.bit_length())):
            @pl.when(n & k != 0)
            def _(rows=buf.at[slot, pl.ds(0, k * bs)]):
                pltpu.make_async_copy(rows, rows, sem.at[slot]).wait()

    @pl.when(b == 0)
    def _first():
        # rows no copy has filled are multiplied by weights of exactly 0:
        # they must not hold what VMEM held before (0 * NaN)
        buf[...] = jnp.zeros_like(buf)
        cur[0] = 0
        start(0, 0, 0)

    pos = lens_ref[b]
    n_steps = pl.cdiv(n_pages(b), pps)
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    if v_lanes:  # the latent mode: every head over the whole shared row
        qblk = q_ref[...].astype(buf.dtype)
    else:
        # row h of the block-diagonal views owns lanes [h * w, (h + 1) * w)
        row = jax.lax.broadcasted_iota(jnp.int32, (hp, hw), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hw), 1)
        if group == 1:
            diag = (lane >= row * w) & (lane < (row + 1) * w)
            q_rows = q_ref[...]
        else:
            # row r * n_kv + j: query head j * group + r, over K|V head j
            kv = jax.lax.rem(row, n_kv)
            diag = ((lane >= kv * w) & (lane < (kv + 1) * w)
                    & (row < group * n_kv))
            q_rows = jnp.concatenate(
                [jnp.broadcast_to(q_ref[r:r + 1, :], (n_kv, hw))
                 for r in range(group)]
                + [jnp.zeros((hp - group * n_kv, hw), q_ref.dtype)]
                * (hp > group * n_kv))
        qblk = jnp.where(diag, q_rows, 0.0).astype(buf.dtype)  # [hp, hw]

    def step(c, slot):
        nxt = 1 - slot

        @pl.when(c + 1 < n_steps)
        def _():
            start(b, c + 1, nxt)

        @pl.when((c + 1 == n_steps) & (b + 1 < nb))
        def _():
            start(b + 1, 0, nxt)

        wait(b, c, slot)
        rows = buf[slot]  # [step_tokens, hw]: K|V of every head
        s = jax.lax.dot_general(
            qblk, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [hp, step_tokens]
        col = c * step_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos, s, _NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # the latent mode weighs the row's V prefix alone: the lanes behind
        # it (the rotated keys, the padding) are no part of any output
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_lanes] if v_lanes else rows,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [hp, hw or v_lanes]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return nxt

    cur[0] = jax.lax.fori_loop(0, n_steps, step, cur[0])
    # position 0 is never masked, so every sum is positive
    if v_lanes:
        o_ref[...] = acc_scr[...] / l_scr[:, :1]
        return
    out = jnp.where(diag, acc_scr[...] / l_scr[:, :1], 0.0)
    if group == 1:
        o_ref[...] = jnp.sum(out, axis=0, keepdims=True)
    else:
        for r in range(group):
            o_ref[r:r + 1, :] = jnp.sum(out[r * n_kv:(r + 1) * n_kv],
                                        axis=0, keepdims=True)


def _scratch(step: Step, hw: int, hp: int, dtype, v_lanes: int = 0):
    """The kernel's scratch: the step's page buffers, their semaphores, the
    buffer in use, then running maximum, sum and weighted rows of ``hp``
    heads (in the latent mode the rows' leading ``v_lanes`` alone)."""
    return [pltpu.VMEM((step.buffers, step.positions, hw), dtype),
            pltpu.SemaphoreType.DMA((step.buffers,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hp, _LANES), jnp.float32),
            pltpu.VMEM((hp, _LANES), jnp.float32),
            pltpu.VMEM((hp, v_lanes or hw), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_attention(q, pool, tables, context_lens, *, scale, interpret):
    B, H, hd = q.shape
    _, bs, hw = pool.shape
    w = 2 * hd
    n_kv = hw // w
    group = H // n_kv
    max_blocks = tables.shape[1]
    step = step_schedule(hw * pool.dtype.itemsize)
    hp = _round_up(H, _sublanes(pool.dtype))
    # q over the K lanes of its head, zeros over the V lanes; float32
    # holds the model's values exactly and is what the kernel selects on
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, 0), (0, hd)))
    if group > 1:  # group-major lines: see the module's docstring
        qp = qp.reshape(B, n_kv, group, w).transpose(0, 2, 1, 3)
    row = pl.BlockSpec((None, group, hw), lambda b, *_: (b, 0, 0))
    o = pl.pallas_call(
        functools.partial(_kernel, block_size=bs,
                          pages_per_step=step.positions // bs,
                          max_blocks=max_blocks, head_lanes=w, scale=scale,
                          n_kv=n_kv, group=group, batched=step.batched),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=_scratch(step, hw, hp, pool.dtype)),
        out_shape=jax.ShapeDtypeStruct((B, group, hw), jnp.float32),
        # the buffers and the copy in flight carry over from slot to slot
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.reshape(-1).astype(jnp.int32), context_lens.astype(jnp.int32),
      qp.reshape(B, group, hw), pool)
    if group > 1:  # back to query-head order
        o = o.reshape(B, group, n_kv, w).transpose(0, 2, 1, 3)
    return o.reshape(B, H, w)[..., hd:].reshape(B, H * hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "v_lanes", "interpret"))
def _paged_latent_attention(q, pool, tables, context_lens, *, scale, v_lanes,
                            interpret):
    B, H, r = q.shape
    _, bs, hw = pool.shape
    max_blocks = tables.shape[1]
    step = step_schedule(hw * pool.dtype.itemsize)
    hp = _round_up(H, _sublanes(pool.dtype))
    # whole tiles: zeros over the row's padding lanes and the padding heads
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, hp - H), (0, hw - r)))
    o = pl.pallas_call(
        functools.partial(_kernel, block_size=bs,
                          pages_per_step=step.positions // bs,
                          max_blocks=max_blocks, head_lanes=hw, scale=scale,
                          v_lanes=v_lanes, batched=step.batched),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, hp, hw), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, hp, v_lanes),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=_scratch(step, hw, hp, pool.dtype, v_lanes)),
        out_shape=jax.ShapeDtypeStruct((B, hp, v_lanes), jnp.float32),
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(tables.reshape(-1).astype(jnp.int32), context_lens.astype(jnp.int32),
      qp, pool)
    return o[:, :H].astype(q.dtype)


def paged_latent_attention(q, pool, tables, context_lens, scale, v_lanes,
                           interpret=None):
    """Attention of one new token a slot over that slot's paged context
    where every head shares ONE row a position (the module's docstring).

    q ``[B, H, r]``: each head's query over the row's leading ``r`` lanes
    as they lie (the up-projection absorbed, the rotated lanes behind it);
    ``pool`` ``[rows, block_size, row_lanes]`` with ``row_lanes >= r``
    whole 128-lane tiles, zeros behind lane ``r``; ``tables`` and
    ``context_lens`` as :func:`paged_attention` has them. Returns ``[B, H,
    v_lanes]`` in q's dtype: per head the softmax-weighted sum of the
    rows' leading ``v_lanes``, which the caller puts through the value
    up-projection. Scores, statistics and the sum are float32."""
    why = unsupported(0, pool.shape[1], pool.dtype,
                      latent=(pool.shape[2], v_lanes))
    if why:
        raise ValueError(f"paged_latent_attention: {why}")
    if interpret is None:
        interpret = not on_tpu()
    return _paged_latent_attention(
        q, pool, tables, context_lens, scale=float(scale),
        v_lanes=int(v_lanes), interpret=bool(interpret))


def paged_attention(q, pool, tables, context_lens, scale, interpret=None):
    """Attention of one new token a slot over that slot's paged context.

    q ``[B, H, hd]`` (normed and rotated as the block has it); ``pool``
    the KV pool as it rests, ``[rows, block_size, H_kv * 2 * hd]`` (``H_kv
    == H``, or fewer K|V heads that groups of query heads share);
    ``tables`` ``[B, max_blocks]`` the pool row-block of each of a slot's
    pages (the layer's base already added); ``context_lens`` ``[B]`` the
    position of the new token, whose K and V are in the pool already:
    positions ``0 .. context_lens[b]`` are attended, the pages behind
    them are never read. Returns ``o [B, H * hd]`` in q's dtype; scores,
    softmax statistics and the weighted sum are float32. On a non-TPU
    backend the kernel runs in the pallas interpreter.
    """
    why = unsupported(q.shape[-1], pool.shape[1], pool.dtype, q.shape[1],
                      pool.shape[2] // (2 * q.shape[-1]))
    if why:
        raise ValueError(f"paged_attention: {why}")
    if interpret is None:
        interpret = not on_tpu()
    return _paged_attention(q, pool, tables, context_lens,
                            scale=float(scale), interpret=bool(interpret))
