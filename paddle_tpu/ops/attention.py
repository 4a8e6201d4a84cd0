"""Attention ops — TPU-native fused attention.

No reference twin: goodcoder-cnn/Paddle predates fused attention (its
`operators/fused/` has only multihead_matmul fusions for inference). On TPU
the fused softmax(QK^T)V is the single hottest transformer op, so it is a
first-class op here, with a pallas flash-attention kernel for long
sequences (paddle_tpu/ops/pallas/flash_attention.py) and an XLA einsum path
for short or untileable shapes, which is also the reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .common import maybe


_xla_path_warned = set()

# trace-time count of fused_attention_tpu lowerings that dispatched to the
# pallas flash kernel — chip_smoke.py and bench.py assert the long-seq
# config actually ran the flash path
FLASH_DISPATCH_COUNT = 0


def _warn_xla_path(reason: str) -> None:
    """One warning per distinct reason a flash-length sequence runs the
    XLA einsum path because its shape does not tile."""
    if reason not in _xla_path_warned:
        _xla_path_warned.add(reason)
        import logging

        logging.getLogger(__name__).warning(
            "fused_attention_tpu: using the XLA einsum path: %s", reason
        )


def _sdpa_xla(q, k, v, mask=None, is_causal=False, scale=None, layout="BHTD"):
    """Plain XLA path; fp32 softmax accumulator. layout BHTD = (B,H,T,D),
    BTHD = (B,T,H,D) — the latter avoids explicit head transposes by
    putting the head batch dim inside the dot_general (XLA folds the
    shuffle into the matmul's data movement)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qk = "bqhd,bkhd->bhqk" if layout == "BTHD" else "bhqd,bhkd->bhqk"
    pv = "bhqk,bkhd->bqhd" if layout == "BTHD" else "bhqk,bhkd->bhqd"
    logits = jnp.einsum(qk, q, k).astype(jnp.float32) * scale
    if is_causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((tq, tk), jnp.bool_), tk - tq)
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(pv, probs, v)


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, training=True):
    """Functional entry used by nn.functional; dispatches through the op so
    dygraph records it."""
    from .api import dispatch

    ins = {"Q": q, "K": k, "V": v}
    if attn_mask is not None:
        ins["Mask"] = attn_mask
    return dispatch(
        "fused_attention_tpu", ins,
        {"dropout_p": float(dropout_p), "is_causal": bool(is_causal), "is_test": not training},
        ("Out",),
    )


# Tile table of the flash kernels, by what the op can see when it is traced
# (sequence lengths, layout, causal): per kernel the (bq candidates, bk
# candidates), of which the first that divides the length is taken. Swept on
# a TPU v5e by PR 35 with tools/flash_sweep.py at the shape the benchmark
# cell gpt2s-train-1k runs (B 32, T 1024, 12 heads of 64, BTHD, causal),
# each kernel alone and then the whole train step, which decides (PERF.md
# section 6 has the numbers). What the sweep taught: a kernel's time is the
# score area it computes PLUS a cost per accumulator row and step of its
# sequential sweep (running max and sum, the accumulator's rescale and round
# trip, the row statistics' transposes), and at heads of 64 the second is
# as large as the first: tiles of 256 x 256 computed 62.5% of the score
# square where the tiles before computed 100% / 75%, and LOST 8.6% of the
# train step. So where the widest tile covers the sweep (lengths up to
# 1024) the forward and dq take ONE kv step, which the kernels run without
# the running statistics' round trip and trimmed to what lies under the
# diagonal (ops/pallas/flash_attention.py: `single`, `_trims`); dkv, which
# keeps no running statistics, takes two q steps of 512. Since PR 53 that
# one-step causal BTHD forward LOOPS over its head groups where the heads
# fill whole lane tiles (`_fwd_looped_kernel_bthd`) and PR 53 swept its q
# tile again, the long ones first, which PR 35 could not judge under an
# unrolled kernel's code size: 256 rows still win the step, because 512
# and 1024 rows compute 12 and 16 squares of 256 where 256 rows compute 10
# and the loop leaves a head's cost a score element as it was (PERF.md
# section 6 has each tile's kernel and step time). Longer sequences
# and non-causal calls keep the tiles they had (from a T = 2048 sweep before
# PR 1; re-measured by PR 35 at T 2048, B 16: still the best of those tried).
# "bwd" (PR 43, the same tool and shape): the ONE fused backward kernel's kv
# tile candidates (its q tile is the whole sequence, trimmed to the rows
# under the diagonal), taken where the lengths are equal and
# flash_attention.fused_bwd_fits says the call's heads fit it; "dq" / "dkv"
# are what such a call falls back to. It won the train step (253.5 ms; with
# q tiles outside and dk / dv accumulated 254.3; two q steps of 512 256.4;
# the two kernels 266.4). Through the op only T 1024 reaches it (the XLA
# path runs below FLASH_MIN_SEQ); the shorter lengths were measured as
# kernels alone (ms a call against the two kernels': T 768 2.33 | 4.54,
# T 512 2.17 | 3.06, T 256 1.87 | 2.64).
_WIDE = (1024, 512, 256, 128)
_FLASH_TILES = {
    # (layout, causal, lengths <= 1024): {kernel: (bq candidates, bk candidates)}
    ("BTHD", True, True): {"fwd": ((256, 128), _WIDE), "dq": ((128,), _WIDE),
                           "dkv": ((512, 256, 128), (256, 128)),
                           "bwd": (256,)},
    ("BTHD", True, False): {"fwd": ((256, 128), _WIDE), "dq": ((512,), (512,)), "dkv": ((512,), (512,))},
    ("BTHD", False, True): {"fwd": ((256, 128), _WIDE), "dq": ((512,), (512,)), "dkv": ((512,), (512,))},
    ("BHTD", True, True): {"fwd": (_WIDE, _WIDE), "dq": ((512, 256, 128), _WIDE), "dkv": ((512, 256, 128), _WIDE)},
    ("BHTD", True, False): {"fwd": ((512, 256, 128), _WIDE)},
    ("BHTD", False, True): {"fwd": ((512, 256, 128), _WIDE)},
}
_FLASH_TILES["BTHD", False, False] = _FLASH_TILES["BTHD", False, True]
_FLASH_TILES["BHTD", False, False] = _FLASH_TILES["BHTD", False, True]


def _first_dividing(cands, n):
    return next((b for b in cands if n % b == 0), None)


def _flash_tiles(tq, tk, layout, causal, heads=1, head_dim=128):
    """(bq, bk, bwd_blocks) of the table above for one call; bq or bk is
    None where no candidate divides the length (the XLA path then), and
    bwd_blocks where a backward kernel has no entry or none of its
    candidates divides (the backward then takes the forward's tiles).
    bwd_blocks is ("fused", bk) where the table has a "bwd" entry, the
    lengths are equal and the ONE fused backward kernel fits the call's
    `heads` of `head_dim` (its VMEM budget, its loop over heads); the two
    kernels' four tiles otherwise."""
    table = _FLASH_TILES[layout, bool(causal), max(tq, tk) <= _WIDE[0]]
    pick = lambda kernel: tuple(  # noqa: E731
        _first_dividing(c, n) for c, n in zip(table.get(kernel, ((), ())), (tq, tk)))
    bwd = pick("dq") + pick("dkv")
    if "bwd" in table and tq == tk:
        from .pallas.flash_attention import fused_bwd_fits

        fused_bk = _first_dividing(table["bwd"], tk)
        if fused_bk and fused_bwd_fits(fused_bk, tq, heads, head_dim):
            bwd = ("fused", fused_bk)
    return pick("fwd") + (None if None in bwd else bwd,)


# The flash kernels take sequences from here up (the shape the benchmark
# cell gpt2s-train-1k trains at and PR 35 swept); below it XLA's fused
# attention runs. The crossover itself was measured before PR 1 on another
# installation (XLA won at T=512, where the flash grid overhead dominates)
# and not since.
FLASH_MIN_SEQ = 1024


@register_op("fused_attention_tpu", no_grad_inputs=("Mask",), uses_rng=True)
def _fused_attention_tpu(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = maybe(ins, "Mask")
    is_causal = attrs.get("is_causal", False)
    layout = attrs.get("layout", "BHTD")  # BTHD: heads stay in place, no
    # explicit transpose ops around the attention (profiled ~10% of the
    # GPT step); the head batch dim rides inside the dot_generals
    use_flash = attrs.get("use_flash", True)
    seq_ax = 1 if layout == "BTHD" else 2

    # context parallelism: with a mesh carrying the sequence axis, run the
    # ring-attention shard_map schedule (sequence sharded, K/V streamed
    # over ICI with ppermute) instead of full-sequence attention
    seq_axis = attrs.get("sequence_parallel_axis", "")
    mesh = getattr(ctx, "mesh", None)
    out = None
    if seq_axis and mesh is not None and seq_axis in mesh.axis_names and mask is None:
        from ..parallel.ring_attention import ring_attention

        b_axis = attrs.get("batch_parallel_axis", "dp")
        sp_size = mesh.shape[seq_axis]
        dp_size = mesh.shape.get(b_axis, 1)
        if q.shape[seq_ax] % sp_size != 0 or q.shape[0] % dp_size != 0:
            raise ValueError(
                f"ring attention needs seq divisible by mesh axis "
                f"{seq_axis!r} ({q.shape[seq_ax]} % {sp_size}) and batch by "
                f"{b_axis!r} ({q.shape[0]} % {dp_size}); pad the sequence "
                f"or adjust the mesh"
            )
        rq, rk, rv = (
            (q, k, v) if layout == "BHTD"
            else (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        )
        out = ring_attention(
            rq, rk, rv, mesh, seq_axis=seq_axis, batch_axis=b_axis,
            causal=is_causal,
        )
        if layout == "BTHD":
            out = out.transpose(0, 2, 1, 3)
    # GSPMD cannot partition a Mosaic call, and the flash kernel has no
    # shard_map region of its own yet: a mesh program that did not take
    # the ring path above runs the XLA einsum, which GSPMD partitions
    single_device = mesh is None or mesh.size == 1
    if out is None and use_flash and single_device and mask is None and q.shape[seq_ax] >= FLASH_MIN_SEQ and q.shape[-1] in (64, 128, 256):
        tq, tk = q.shape[seq_ax], k.shape[seq_ax]
        bq, bk, bwd_blocks = _flash_tiles(tq, tk, layout, is_causal,
                                          heads=q.shape[3 - seq_ax], head_dim=q.shape[-1])
        # a sweep's own tiles before the table's (tools/flash_sweep.py step
        # sets them on the op): the forward's, which the backward then takes
        # too unless `bwd_blocks` names its own, one kv tile for the ONE fused
        # kernel or dq's and dkv's four
        if attrs.get("block_q"):
            bq, bk, bwd_blocks = int(attrs["block_q"]), int(attrs["block_k"]), None
        if attrs.get("bwd_blocks"):
            own = tuple(int(b) for b in attrs["bwd_blocks"])
            bwd_blocks = ("fused",) + own if len(own) == 1 else own
        if bq is None or bk is None:
            _warn_xla_path(f"seq lengths ({tq},{tk}) not divisible by 128")
        else:
            # no try/except: once the shape selects the kernel, a kernel
            # that fails to trace is an error on every backend — the XLA
            # path is a shape-based choice, never a rescue
            from .pallas.flash_attention import flash_attention

            # both layouts are native kernel tilings — no transposes
            out = flash_attention(
                q, k, v, causal=is_causal, block_q=bq, block_k=bk,
                layout=layout, bwd_blocks=bwd_blocks,
            )
            global FLASH_DISPATCH_COUNT
            FLASH_DISPATCH_COUNT += 1
    if out is None:
        out = _sdpa_xla(q, k, v, mask, is_causal, layout=layout)
    p = attrs.get("dropout_p", 0.0)
    if p and not attrs.get("is_test", False):
        keep = jax.random.bernoulli(ctx.rng(attrs.get("_rng_id", 0)), 1.0 - p, out.shape)
        out = jnp.where(keep, out / (1.0 - p), 0.0).astype(out.dtype)
    return {"Out": out}
