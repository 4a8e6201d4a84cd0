"""Plain reference of the LFM2-MoE block (``Lfm2MoeForCausalLM``).

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no KV cache, no conv
state, no batching, no sharding: one full causal forward over ``[1, T]``.
Layer by layer, with ``u = rms(h, g_op)`` and ``m = rms(h, g_ffn)``:

    h <- h + Op_l(u);  h <- h + FF_l(m);  logits = rms(h_L, g_out) E^T

``Op_l`` of a ``conv`` layer (the gated short convolution)

    [B | C | X] = u W_in                       (chunked in that order)
    z_t = B_t * X_t
    c_t = sum_j w[j] * z_{t - (K - 1) + j},    z_{<0} = 0   (K taps, depthwise)
    Op  = (C_t * c_t) W_out

written as the explicit K-term shifted sum; of a ``full_attention`` layer

    q = heads(u W_q), k = heads(u W_k), v = heads(u W_v)   (H, H_kv, H_kv heads)
    q, k = rope(rms(q, g_q)), rope(rms(k, g_k))            (rms over each head's lanes)
    Op  = W_o . softmax(q k^T / sqrt(hd), causal) v,  query head i over K|V head i // (H / H_kv)

with the scores materialised and K and V repeated ``H / H_kv`` times.
``FF_l`` of the leading dense layers is ``(silu(m W_1) * (m W_3)) W_2``; of
the others

    s = sigmoid(m W_r)                         float32
    the k experts: the top-k of s + b          (b selects, and only selects)
    w_e = s_e / (sum over the k of s + 1e-6) * routed_scaling_factor
    FF  = sum_e w_e (silu(m G_e) * (m U_e)) D_e

computed as EVERY expert on every token under weights that are zero off a
token's top-k, one expert after another.

Departures from the published forward: none of mathematics. The published
code keeps activations in bfloat16 and runs the convolution as a grouped
``conv1d``; here everything is float32 and the convolution is the sum it
stands for. For memory only (a model that fills the chip leaves ~4 GB): the
scores are made for 512 query positions at a time, each expert's weights
are cast to float32 as its turn comes, and the embedding rows are gathered
before the cast.

The only thing shared with the program is the NAMES (and so the shapes) of
the weights: ``gpt.h<i>.conv.taps.w`` is ``[K, D]``, tap ``j`` on the input
``K - 1 - j`` positions back (the published ``conv.weight[:, 0, j]``).
``matmul_dtype`` exists for the yardstick's own check: the same forward
with its matmul operands rounded to a lower precision has to come out as
NOT correct.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG = -1e30
_Q_BLOCK = 512  # query positions whose scores are alive at once

_OP_KEYS = {"conv": ("conv.in_proj.w", "conv.taps.w", "conv.out_proj.w"),
            "full_attention": ("attn.q.w", "attn.k.w", "attn.v.w", "attn.proj.w",
                               "attn.q_norm.scale", "attn.k_norm.scale")}
_FF_KEYS = {"dense": ("mlp.gate.w", "mlp.up.w", "mlp.down.w"),
            "moe": ("moe.router.w", "moe.router.bias", "moe.gate.w", "moe.up.w", "moe.down.w")}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(a, g, eps):
    return a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps) * g


def _rotate_half(a):
    half = a.shape[-1] // 2
    return jnp.concatenate([-a[..., half:], a[..., :half]], axis=-1)


def _rope(a, theta: float):
    """a [T, H, hd] turned at positions 0..T-1."""
    hd = a.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return a * jnp.cos(emb) + _rotate_half(a) * jnp.sin(emb)


def _top_k(p, k: int):
    """Ids of the k largest of p [N, E], largest first, by k passes of
    argmax (the lowest index wins a tie)."""
    idxs = []
    for _ in range(k):
        i = jnp.argmax(p, axis=-1)
        idxs.append(i)
        p = p.at[jnp.arange(p.shape[0]), i].set(-jnp.inf)
    return jnp.stack(idxs, axis=-1)


def _mm(matmul_dtype):
    def mm(a, b):
        if matmul_dtype is not None:
            a, b = (x.astype(matmul_dtype).astype(jnp.float32) for x in (a, b))
        return a @ b
    return mm


def _conv(u, w, mm):
    """The gated short convolution on u [T, D]."""
    taps = _f32(w["conv.taps.w"])                     # [K, D]
    K, T = taps.shape[0], u.shape[0]
    b, c, x = jnp.split(mm(u, _f32(w["conv.in_proj.w"])), 3, axis=-1)
    z = b * x
    conv = jnp.zeros_like(z)
    for j in range(K):                                # tap j: K - 1 - j positions back
        back = K - 1 - j
        conv = conv + taps[j] * jnp.pad(z, ((back, 0), (0, 0)))[:T]
    return mm(c * conv, _f32(w["conv.out_proj.w"]))


def _attention(u, w, mm, n_head: int, n_kv_head: int, eps: float, theta: float):
    T, D = u.shape
    hd = D // n_head
    q = mm(u, _f32(w["attn.q.w"])).reshape(T, n_head, hd)
    k = mm(u, _f32(w["attn.k.w"])).reshape(T, n_kv_head, hd)
    v = mm(u, _f32(w["attn.v.w"])).reshape(T, n_kv_head, hd)
    q = _rope(_rms(q, _f32(w["attn.q_norm.scale"]), eps), theta)
    k = _rope(_rms(k, _f32(w["attn.k_norm.scale"]), eps), theta)
    k, v = (jnp.repeat(a, n_head // n_kv_head, axis=1) for a in (k, v))
    pos = jnp.arange(T)
    qb = _Q_BLOCK if T % _Q_BLOCK == 0 else T

    def rows(args):
        q_rows, q_pos = args                          # [qb, H, hd], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) / math.sqrt(hd)
        s = jnp.where((q_pos[:, None] >= pos[None, :])[None], s, _NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(rows, (q.reshape(T // qb, qb, n_head, hd), pos.reshape(T // qb, qb)))
    return mm(o.reshape(T, D), _f32(w["attn.proj.w"]))


def _experts(m, w, mm, top_k: int, scale: float):
    """(FF [T, D], the top-k expert ids [T, k]) of normed hidden m [T, D]."""
    s = jax.nn.sigmoid(mm(m, _f32(w["moe.router.w"])))
    e_top = _top_k(s + _f32(w["moe.router.bias"]), top_k)
    s_top = jnp.take_along_axis(s, e_top, axis=-1)
    w_top = s_top / (jnp.sum(s_top, axis=-1, keepdims=True) + 1e-6) * scale
    dense = jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], e_top].set(w_top)

    def one(acc, e):                                  # every token through expert e
        gate, up, down, w_e = e
        a = jax.nn.silu(mm(m, _f32(gate))) * mm(m, _f32(up))
        return acc + w_e[:, None] * mm(a, _f32(down)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (w["moe.gate.w"], w["moe.up.w"], w["moe.down.w"], dense.T))
    return out, e_top


@functools.partial(jax.jit, static_argnames=("op", "ff", "n_head", "n_kv_head", "top_k", "eps",
                                             "theta", "scale", "matmul_dtype"))
def _block(h, w, op: str, ff: str, n_head: int, n_kv_head: int, top_k: int, eps: float,
           theta: float, scale: float, matmul_dtype=None):
    """One layer. h [T, D] float32; w: this layer's arrays under their short
    names, any dtype. Returns (h', top-k expert ids [T, k] or None)."""
    mm = _mm(matmul_dtype)
    u = _rms(h, _f32(w["ln1.scale"]), eps)
    if op == "conv":
        h = h + _conv(u, w, mm)
    else:
        h = h + _attention(u, w, mm, n_head, n_kv_head, eps, theta)
    m = _rms(h, _f32(w["ln2.scale"]), eps)
    if ff == "dense":
        a = jax.nn.silu(mm(m, _f32(w["mlp.gate.w"]))) * mm(m, _f32(w["mlp.up.w"]))
        return h + mm(a, _f32(w["mlp.down.w"])), None
    y, e_top = _experts(m, w, mm, top_k, scale)
    return h + y, e_top


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_at(x, g, head, positions, eps: float):
    return _rms(x[positions], _f32(g), eps) @ _f32(head).T


def hidden(get, tokens, layer_types, num_dense_layers: int, n_head: int, n_kv_head: int,
           top_k: int, eps: float = 1e-5, theta: float = 1e6, scale: float = 1.0,
           matmul_dtype=None):
    """(final residual stream [T, D] of ``tokens`` [T], before the last
    norm; the top-k expert ids of every position and expert layer [T,
    L_moe, k])."""
    routing = []
    with jax.default_matmul_precision("highest"):
        x = _f32(get("gpt.wte")[tokens])
        for i, op in enumerate(layer_types):
            ff = "dense" if i < num_dense_layers else "moe"
            keys = ("ln1.scale", "ln2.scale") + _OP_KEYS[op] + _FF_KEYS[ff]
            x, e_top = _block(x, {k: get(f"gpt.h{i}.{k}") for k in keys}, op=op, ff=ff,
                              n_head=n_head, n_kv_head=n_kv_head, top_k=top_k, eps=eps,
                              theta=theta, scale=scale, matmul_dtype=matmul_dtype)
            if e_top is not None:
                routing.append(e_top)
    return x, jnp.stack(routing, axis=1)


def logits_at(get, tokens, positions, **kw):
    """(next-token logits [1, P, V] at ``positions`` [1, P] of ``tokens``
    [1, T], teacher-forced: position p sees tokens 0..p, the head tied to
    the embedding; the routing of :func:`hidden` as [1, T, L_moe, k])."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference takes one sequence at a time: no batching")
    eps = kw.get("eps", 1e-5)
    x, routing = hidden(get, tokens[0], **kw)
    with jax.default_matmul_precision("highest"):
        logits = _logits_at(x, get("gpt.lnf.scale"), get("gpt.wte"), positions[0], eps=eps)
    return logits[None], routing[None]
