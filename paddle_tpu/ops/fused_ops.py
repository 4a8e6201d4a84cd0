"""Fused op family.

Reference: paddle/fluid/operators/fused/*. On TPU most of these exist for
API parity only — XLA re-fuses the composed graph anyway — but they matter
for loading reference inference programs, which emit them from fuse passes.
Padded-batch deviations from LoD inputs are documented per op.
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import register_op
from .common import maybe, x

_UNARY = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "identity": lambda v: v,
    "": lambda v: v,
}

_BINARY = {
    "elementwise_add": jnp.add,
    "elementwise_sub": jnp.subtract,
    "elementwise_mul": jnp.multiply,
}


@register_op("fused_elemwise_activation")
def _fused_elemwise_activation(ctx, ins, attrs):
    """functor_list = [outer, inner] (fused_elemwise_activation_op.h):
    binary+unary -> out = f_bin(x, f_un(y)); unary+binary -> f_un(f_bin)."""
    xv, yv = ins["X"][0], ins["Y"][0]
    functors = [f.split(",")[0] for f in attrs["functor_list"]]
    outer, inner = functors[0], functors[1]
    if outer in _BINARY:
        mid = _UNARY[inner](yv)
        out = _BINARY[outer](xv, mid)
    else:
        mid = _BINARY[inner](xv, yv)
        out = _UNARY[outer](mid)
    return {"Out": out, "IntermediateOut": mid}


@register_op("fused_embedding_seq_pool", no_grad_inputs=("Ids",))
def _fused_embedding_seq_pool(ctx, ins, attrs):
    """lookup_table + sum sequence_pool in one op
    (fused_embedding_seq_pool_op.h). Ids: (B, T) padded, -1 = pad slot."""
    w, ids = ins["W"][0], ins["Ids"][0]
    if ids.ndim == 3 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    valid = (ids >= 0)[..., None]
    emb = w[jnp.clip(ids, 0, w.shape[0] - 1)]
    return {"Out": jnp.sum(jnp.where(valid, emb, 0.0), axis=1)}


@register_op("fused_fc_elementwise_layernorm")
def _fused_fc_elementwise_layernorm(ctx, ins, attrs):
    """fc -> + residual Y -> layer_norm (fused_fc_elementwise_layernorm_op)."""
    v, w, yv = ins["X"][0], ins["W"][0], ins["Y"][0]
    bias0 = maybe(ins, "Bias0")
    scale, bias1 = maybe(ins, "Scale"), maybe(ins, "Bias1")
    eps = attrs.get("epsilon", 1e-5)
    out = v.reshape(-1, w.shape[0]) @ w
    if bias0 is not None:
        out = out + bias0
    out = out.reshape(yv.shape) + yv
    mean = jnp.mean(out, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(out - mean), axis=-1, keepdims=True)
    norm = (out - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        norm = norm * scale
    if bias1 is not None:
        norm = norm + bias1
    return {"Out": norm, "Mean": mean[..., 0], "Variance": var[..., 0]}


@register_op("fused_batch_norm_act", no_grad_inputs=("Mean", "Variance"))
def _fused_batch_norm_act(ctx, ins, attrs):
    from .nn_ops import _batch_norm

    out = _batch_norm(ctx, ins, attrs)
    act = _UNARY[attrs.get("act_type", "relu")]
    out["Y"] = act(out["Y"])
    return out


@register_op("fused_embedding_eltwise_layernorm", no_grad_inputs=("Ids",))
def _fused_embedding_eltwise_layernorm(ctx, ins, attrs):
    """Sum of N embedding lookups + layer_norm (BERT embedding fuse)."""
    embs = ins["Embs"]
    ids = ins["Ids"]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    eps = attrs.get("epsilon", 1e-5)
    acc = None
    for w, i in zip(embs, ids):
        if i.ndim == 3 and i.shape[-1] == 1:
            i = i[..., 0]
        e = w[i.astype(jnp.int32)]
        acc = e if acc is None else acc + e
    mean = jnp.mean(acc, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(acc - mean), axis=-1, keepdims=True)
    return {"Out": (acc - mean) * jax.lax.rsqrt(var + eps) * scale + bias}


@register_op("multihead_matmul")
def _multihead_matmul(ctx, ins, attrs):
    """Fused QKV attention for inference (fused/multihead_matmul_op.cu):
    Input (B, S, C), W (C, 3C), Bias (3C), optional BiasQK added to the
    scaled logits; alpha is the 1/sqrt(dk) scale."""
    v, w, bias = ins["Input"][0], ins["W"][0], ins["Bias"][0]
    bias_qk = maybe(ins, "BiasQK")
    heads = attrs["head_number"]
    alpha = attrs.get("alpha", 1.0)
    b, s, c = v.shape
    qkv = v @ w.reshape(c, -1) + bias.reshape(-1)
    q, k, val = jnp.split(qkv, 3, axis=-1)

    def heads_split(t):
        return t.reshape(b, s, heads, c // heads).transpose(0, 2, 1, 3)

    q, k, val = heads_split(q), heads_split(k), heads_split(val)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * alpha
    if bias_qk is not None:
        logits = logits + bias_qk
    attn = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", attn, val)
    return {"Out": out.transpose(0, 2, 1, 3).reshape(b, s, c)}


@register_op("fusion_gru", no_grad_inputs=("H0",))
def _fusion_gru(ctx, ins, attrs):
    """x-projection + GRU in one op (fused/fusion_gru_op.cc). Padded
    (B, T, D_in) deviation from the reference's LoD packing."""
    from .rnn_ops import _gru

    xv = ins["X"][0]
    wx = ins["WeightX"][0]  # (D_in, 3D)
    proj = jnp.einsum("btd,dk->btk", xv, wx)
    out = _gru(ctx, {
        "Input": [proj], "Weight": ins["WeightH"],
        "Bias": ins.get("Bias", []), "H0": ins.get("H0", []),
    }, attrs)
    return {"Hidden": out["Hidden"], "XX": proj,
            "ReorderedH0": jnp.zeros_like(out["Hidden"][:, 0]),
            "BatchedInput": proj, "BatchedOut": out["Hidden"]}


@register_op("fusion_lstm", no_grad_inputs=("H0", "C0"))
def _fusion_lstm(ctx, ins, attrs):
    from .rnn_ops import _lstm

    xv = ins["X"][0]
    wx = ins["WeightX"][0]  # (D_in, 4D)
    proj = jnp.einsum("btd,dk->btk", xv, wx)
    out = _lstm(ctx, {
        "Input": [proj], "Weight": ins["WeightH"],
        "Bias": ins.get("Bias", []),
        "H0": ins.get("H0", []), "C0": ins.get("C0", []),
    }, attrs)
    return {"Hidden": out["Hidden"], "Cell": out["Cell"], "XX": proj,
            "BatchedInput": proj, "BatchedHidden": out["Hidden"],
            "BatchedCell": out["Cell"],
            "ReorderedH0": jnp.zeros_like(out["Hidden"][:, 0]),
            "ReorderedC0": jnp.zeros_like(out["Cell"][:, 0])}


@register_op("fusion_seqpool_concat", no_grad_inputs=("Length",))
def _fusion_seqpool_concat(ctx, ins, attrs):
    """sequence_pool over each input then concat (fusion_seqpool_concat_op).
    Padded (B, T, D) inputs; one shared Length or none."""
    from .sequence_ops import _sequence_pool

    lengths = ins.get("Length", [])
    pooled = []
    for v in ins["X"]:
        sub = {"X": [v]}
        if lengths:
            sub["Length"] = lengths
        pooled.append(_sequence_pool(ctx, sub, {
            "pooltype": attrs.get("pooltype", "SUM")})["Out"])
    return {"Out": jnp.concatenate(pooled, axis=-1)}


@register_op("fusion_seqpool_cvm_concat", no_grad_inputs=("CVM", "Length"))
def _fusion_seqpool_cvm_concat(ctx, ins, attrs):
    from .misc_ops import _cvm
    from .sequence_ops import _sequence_pool

    lengths = ins.get("Length", [])
    outs = []
    for v in ins["X"]:
        sub = {"X": [v]}
        if lengths:
            sub["Length"] = lengths
        p = _sequence_pool(ctx, sub, {"pooltype": attrs.get("pooltype", "SUM")})["Out"]
        outs.append(_cvm(ctx, {"X": [p], "CVM": ins.get("CVM", [])},
                         {"use_cvm": attrs.get("use_cvm", True)})["Y"])
    return {"Out": jnp.concatenate(outs, axis=-1)}


@register_op("fusion_repeated_fc_relu")
def _fusion_repeated_fc_relu(ctx, ins, attrs):
    v = x(ins)
    out = v
    for w, b in zip(ins["W"], ins["Bias"]):
        out = jax.nn.relu(out.reshape(-1, w.shape[0]) @ w + b.reshape(1, -1))
    return {"Out": out, "ReluOut": [out] * max(len(ins["W"]) - 1, 0)}


@register_op("fusion_squared_mat_sub")
def _fusion_squared_mat_sub(ctx, ins, attrs):
    """(x@y)^2 - x^2@y^2, scaled (fusion_squared_mat_sub_op.cc)."""
    a, b = ins["X"][0], ins["Y"][0]
    scalar = attrs.get("scalar", 1.0)
    ab = a @ b
    sq = (a * a) @ (b * b)
    return {"Out": scalar * (ab * ab - sq), "SquaredX": a * a,
            "SquaredY": b * b, "SquaredXY": ab * ab}


@register_op("fusion_seqconv_eltadd_relu", no_grad_inputs=("Length",))
def _fusion_seqconv_eltadd_relu(ctx, ins, attrs):
    from .sequence_ops import _sequence_conv

    sub = {"X": ins["X"], "Filter": ins["Filter"]}
    if ins.get("Length"):
        sub["Length"] = ins["Length"]
    out = _sequence_conv(ctx, sub, {
        "contextStart": attrs.get("contextStart", 0),
        "contextLength": attrs.get("contextLength", 1),
    })["Out"]
    bias = ins["Bias"][0]
    out = jax.nn.relu(out + bias.reshape(1, 1, -1))
    return {"Out": out, "ColMat": jnp.zeros_like(out)}


@register_op("conv2d_fusion")
def _conv2d_fusion(ctx, ins, attrs):
    """conv + bias + activation (+ residual) (fused/conv2d_fusion_op.cc)."""
    from .nn_ops import _conv2d

    out = _conv2d(ctx, {k: v for k, v in ins.items()
                        if k in ("Input", "Filter")}, attrs)["Output"]
    bias = maybe(ins, "Bias")
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    resid = maybe(ins, "ResidualData")
    if resid is not None:
        out = out + resid
    act = _UNARY.get(attrs.get("activation", "relu"), jax.nn.relu)
    return {"Output": act(out)}


# ---------------------------------------------------------------------------
# fused lm-head cross-entropy (no reference twin: the reference's
# softmax_with_cross_entropy_op.cu fuses softmax+CE but still materializes
# the full logits; at GPT vocab sizes the [B*T, V] logits tensor and its
# gradient dominate the lm-head's HBM traffic. Chunking over tokens with
# backward rematerialization keeps only one [C, V] tile live at a time.)
# ---------------------------------------------------------------------------


def _lmhead_pad_and_chunks(n, chunk_size):
    """(padded_n, n_chunks): pad the token count UP to a chunk multiple
    so the [C, V] working-set bound holds for ANY n (a divisor search
    would collapse to one full-logits chunk for prime-ish n, defeating
    the memory guarantee huge-vocab users force the fused path for).
    Pad rows carry label 0 and zero cotangents (the caller slices the
    output), so they change nothing numerically."""
    c = max(1, min(n, int(chunk_size)))
    padded = ((n + c - 1) // c) * c
    return padded, padded // c


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _lm_head_ce(x2d, w, lbl, n_chunks):
    loss, _ = _lm_head_ce_fwd(x2d, w, lbl, n_chunks)
    return loss


def _chunk_logits(xc, w):
    # bf16 matmul, fp32 accumulation (MXU native)
    return jax.lax.dot_general(
        xc, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _lm_head_ce_fwd(x2d, w, lbl, n_chunks):
    n, d = x2d.shape
    c = n // n_chunks
    xs = x2d.reshape(n_chunks, c, d)
    ls = lbl.reshape(n_chunks, c).astype(jnp.int32)

    def body(args):
        xc, lc = args
        logits = _chunk_logits(xc, w)  # (C, V) fp32 — never all chunks at once
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=1)[:, 0]
        return lse - picked

    nll = jax.lax.map(body, (xs, ls))
    return nll.reshape(n), (x2d, w, lbl)


def _lm_head_ce_bwd(n_chunks, res, g):
    x2d, w, lbl = res
    n, d = x2d.shape
    v = w.shape[0]
    c = n // n_chunks
    xs = x2d.reshape(n_chunks, c, d)
    ls = lbl.reshape(n_chunks, c).astype(jnp.int32)
    gs = g.reshape(n_chunks, c)

    def body(dw, args):
        xc, lc, gc = args
        logits = _chunk_logits(xc, w)  # rematerialized
        lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - lse)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
                  == lc[:, None])
        dlog = ((p - onehot.astype(jnp.float32))
                * gc[:, None]).astype(w.dtype)  # (C, V) bf16 for the MXU
        dxc = jax.lax.dot_general(
            dlog, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwc = jax.lax.dot_general(
            dlog, xc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw + dwc, dxc.astype(x2d.dtype)

    dw, dxs = jax.lax.scan(body, jnp.zeros((v, d), jnp.float32), (xs, ls, gs))
    return dxs.reshape(n, d), dw.astype(w.dtype), None


_lm_head_ce.defvjp(_lm_head_ce_fwd, _lm_head_ce_bwd)


def _pallas_shard_plan(ctx, batch: int, vocab: int):
    """How the pallas fused CE should partition under the program's
    sharding recipe: (mesh, batch_axes, vocab_axis, gather_axis), or
    None for the single-device direct call. Mesh programs WITHOUT a
    recipe (hand-sharded dryruns, sp programs) return "chunked" — the
    lax-loop path composes under plain GSPMD propagation, a pallas
    custom call does not."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None or int(np.prod(list(mesh.shape.values()))) <= 1:
        return None
    program = getattr(ctx, "program", None)
    # the planner's AOT scoring lowers candidate layouts without
    # attaching them to the program — the context override keeps its
    # HLO identical to what the executor will actually run
    recipe = (getattr(ctx, "sharding_recipe", None)
              or getattr(program, "_sharding_recipe", None))
    if recipe is None:
        return "chunked"
    # batch axes shard the token rows only when the batch divides; the
    # vocab axis composes only when the weight's vocab dim divides
    # (mesh.clean_spec degrades those shardings the same way)
    batch_axes = tuple(
        a for a in recipe.batch_axes if a in mesh.shape)
    n_batch = 1
    for a in batch_axes:
        n_batch *= int(mesh.shape[a])
    if batch_axes and batch % n_batch != 0:
        batch_axes = ()
    vocab_axis = gather_axis = None
    tp_ax, fsdp_ax = recipe.layout.tp_axis, recipe.layout.fsdp_axis
    if recipe.tp > 1 and vocab % recipe.tp == 0 and tp_ax in mesh.shape:
        # GPT_TP_RULES shard the tied embedding's vocab dim on tp:
        # per-shard kernel + partial-stat all-reduce
        vocab_axis = tp_ax
    elif (recipe.fsdp > 1 and vocab % recipe.fsdp == 0
          and fsdp_ax in mesh.shape):
        # the ZeRO-3 dim-0 catch-all shards the vocab dim on fsdp:
        # gather-at-use, the recipe's standard fsdp convention
        gather_axis = fsdp_ax
    return (mesh, batch_axes, vocab_axis, gather_axis)


@register_op("fused_lm_head_ce", no_grad_inputs=("Label",))
def _fused_lm_head_ce(ctx, ins, attrs):
    """Tied-embedding lm head + softmax CE without the [B, T, V] logits
    tensor. Two implementations behind ``attrs["impl"]``:

    - ``"pallas"`` (the default training loss path since the raw-speed
      round): two kernels, each sweeping vocab tiles in VMEM — the
      logits tile never reaches HBM in either direction. The forward
      (``lmhead_ce_stats``) keeps an online max and sum-exp a row; the
      backward (``lmhead_ce_dw``) rematerializes each tile ONCE from the
      saved logsumexp and feeds both dx and dW from it
      (ops/pallas/fused_lmhead_ce.py; interpret-mode on non-TPU
      backends). Tiles follow the call's (tokens, width);
      ``block_n`` / ``block_v`` override them. Under a sharding recipe
      the kernels run as a manual-SPMD region: per-vocab-shard partial
      stats all-reduced over tp, gather-at-use over fsdp, token rows
      over the batch axes.
    - ``"chunked"``: X (B, T, D) @ W (V, D)^T chunked over tokens, fp32
      streaming logsumexp per chunk, backward rematerializes each chunk
      (a lax-loop — holds one [C, V] tile in HBM per step). Kept as the
      A/B baseline and the GSPMD-propagation fallback for hand-sharded
      mesh programs the pallas custom call cannot compose with.

    Loss matches softmax_with_cross_entropy over
    matmul(X, W, transpose_y=True) (fp32 logsumexp over bf16 logits)."""
    xv = ins["X"][0]
    w = ins["W"][0]
    lbl = ins["Label"][0]
    if lbl.ndim == 3 and lbl.shape[-1] == 1:
        lbl = lbl[..., 0]
    b, t, d = xv.shape
    n = b * t
    x2d = xv.reshape(n, d)
    l1d = lbl.reshape(n)

    impl = str(attrs.get("impl", "chunked")).lower()
    if impl == "pallas":
        from .pallas import fused_lmhead_ce as _plc

        plan = _pallas_shard_plan(ctx, b, int(w.shape[0]))
        kw = {}
        for k in ("block_n", "block_v"):
            if attrs.get(k):
                kw[k] = int(attrs[k])
        if plan is None:
            nll = _plc.lmhead_ce(x2d, w, l1d, **kw)
            return {"Loss": nll.reshape(b, t, 1)}
        if plan != "chunked":
            mesh, batch_axes, vocab_axis, gather_axis = plan
            nll = _plc.lmhead_ce_sharded(
                x2d, w, l1d, mesh, batch_axes=batch_axes,
                vocab_axis=vocab_axis, gather_axis=gather_axis, **kw)
            return {"Loss": nll.reshape(b, t, 1)}
        # fall through: mesh program without a recipe -> chunked path

    padded, n_chunks = _lmhead_pad_and_chunks(n, attrs.get("chunk_size", 4096))
    if padded != n:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((padded - n, d), x2d.dtype)], axis=0)
        l1d = jnp.concatenate(
            [l1d, jnp.zeros((padded - n,), l1d.dtype)], axis=0)
    nll = _lm_head_ce(x2d, w, l1d, n_chunks)[:n]
    return {"Loss": nll.reshape(b, t, 1)}
