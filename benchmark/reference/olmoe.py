"""Plain reference of the OLMoE block (``OlmoeForCausalLM``).

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no KV cache, no
batching, no sorting, no sharding. It follows the Hugging Face
``modeling_olmoe.py`` forward, layer by layer:

    n1 = rms(x, g_1)
    q  = rope(heads(rms(n1 Wq, g_q)));  k = rope(heads(rms(n1 Wk, g_k)))
    h  = x + Wo . softmax(q k^T / sqrt(hd), causal) (n1 Wv)
    n2 = rms(h, g_2);  p = softmax(n2 Wr) over the experts, float32
    x' = h + sum over the top-k experts e, in the order top-k gives them,
             of p_e * Wdown_e(silu(Wgate_e n2) * Wup_e n2)
    logits = rms(x_L, g_f) Wlm

rms(a, g) = a / sqrt(mean(a^2) + eps) * g; the q and k norms act on all
of a token's lanes before the split into heads; rope is rotate-half at
the token's position; p_e is used as it is (``norm_topk_prob`` false).
Every token's experts are applied one after another (a gather of that
token's k experts' weights), which is what "no dropped assignment" means.

Departures from the published forward: none of mathematics. The published
code keeps activations in bfloat16 and casts to float32 inside the norms,
the router and RoPE; here everything is float32, which is what a
reference is for.

The only thing shared with the program is the NAMES of the weights
(``gpt.h<i>.moe.gate.w`` ...): the benchmark hands this module a
``get(name) -> array`` callable over the same seed-made weights. Weights
are cast to float32 one layer at a time, so a bf16 model that fills the
chip never needs a second full copy. ``matmul_dtype`` exists for the
yardstick's own check (benchmark/arch/olmoe.py): the same forward with
its matmul operands rounded to a lower precision has to come out as NOT
correct.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG = -1e30
_LAYER_KEYS = ("ln1.scale", "ln2.scale", "attn.q.w", "attn.k.w", "attn.v.w",
               "attn.proj.w", "attn.q_norm.scale", "attn.k_norm.scale",
               "moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(a, g, eps):
    return a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps) * g


def _rotate_half(a):
    half = a.shape[-1] // 2
    return jnp.concatenate([-a[..., half:], a[..., :half]], axis=-1)


def _rope(a, theta: float):
    """a [B, T, H, hd] turned at positions 0..T-1."""
    hd = a.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = jnp.arange(a.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return a * jnp.cos(emb) + _rotate_half(a) * jnp.sin(emb)


def _top_k(p, k: int):
    """The k largest of p [N, E], largest first, by k passes of argmax
    (the lowest index wins a tie)."""
    vals, idxs = [], []
    for _ in range(k):
        i = jnp.argmax(p, axis=-1)
        vals.append(jnp.take_along_axis(p, i[:, None], axis=-1)[:, 0])
        idxs.append(i)
        p = p.at[jnp.arange(p.shape[0]), i].set(-1.0)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "top_k", "eps", "theta", "matmul_dtype"))
def _block(x, w, n_head: int, top_k: int, eps: float, theta: float, matmul_dtype=None):
    """One layer. x [B, T, D] float32; w: this layer's 12 arrays under
    their short names, any dtype. Returns (x', top-k expert ids [B, T, k])."""
    w = {k: _f32(v) for k, v in w.items()}

    def mm(a, b):
        if matmul_dtype is not None:
            a, b = a.astype(matmul_dtype).astype(jnp.float32), b.astype(matmul_dtype).astype(jnp.float32)
        return a @ b

    B, T, D = x.shape
    hd = D // n_head
    n1 = _rms(x, w["ln1.scale"], eps)
    q = _rms(mm(n1, w["attn.q.w"]), w["attn.q_norm.scale"], eps).reshape(B, T, n_head, hd)
    k = _rms(mm(n1, w["attn.k.w"]), w["attn.k_norm.scale"], eps).reshape(B, T, n_head, hd)
    v = mm(n1, w["attn.v.w"]).reshape(B, T, n_head, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    pos = jnp.arange(T)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, _NEG)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(B, T, D)
    h = x + mm(o, w["attn.proj.w"])
    n2 = _rms(h, w["ln2.scale"], eps).reshape(B * T, D)
    p = jax.nn.softmax(mm(n2, w["moe.router.w"]), axis=-1)
    p_top, e_top = _top_k(p, top_k)

    def one_token(tok, experts, weights):
        """Its k experts, one after another."""
        out = jnp.zeros_like(tok)
        for j in range(top_k):
            e = experts[j]
            a = jax.nn.silu(mm(tok, w["moe.gate.w"][e])) * mm(tok, w["moe.up.w"][e])
            out = out + weights[j] * mm(a, w["moe.down.w"][e])
        return out

    y = jax.lax.map(lambda a: one_token(*a), (n2, e_top, p_top))
    return h + y.reshape(B, T, D), e_top.reshape(B, T, top_k)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_at(x, g, head, positions, eps: float):
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _rms(xs, _f32(g), eps) @ _f32(head)


def hidden(get, tokens, n_layer: int, n_head: int, top_k: int, eps: float = 1e-5,
           theta: float = 10000.0, matmul_dtype=None):
    """(final residual stream [B, T, D], before the last norm; the top-k
    expert ids of every position and layer [B, T, L, k])."""
    routing = []
    with jax.default_matmul_precision("highest"):
        x = _f32(get("gpt.wte"))[tokens]
        for i in range(n_layer):
            w = {k: get(f"gpt.h{i}.{k}") for k in _LAYER_KEYS}
            x, e_top = _block(x, w, n_head=n_head, top_k=top_k, eps=eps, theta=theta,
                              matmul_dtype=matmul_dtype)
            routing.append(e_top)
    return x, jnp.stack(routing, axis=2)


def logits_at(get, tokens, positions, n_layer: int, n_head: int, top_k: int,
              eps: float = 1e-5, theta: float = 10000.0, matmul_dtype=None):
    """(next-token logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, T], teacher-forced: position p sees tokens 0..p; the routing of
    :func:`hidden`)."""
    x, routing = hidden(get, tokens, n_layer, n_head, top_k, eps, theta, matmul_dtype)
    with jax.default_matmul_precision("highest"):
        return _logits_at(x, get("gpt.lnf.scale"), get("gpt.lm_head.w"), positions, eps=eps), routing
