"""Plain reference of the GPT-2 block as this repo runs it.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no KV cache, no
batching tricks, no sharding. It follows the GPT-2 paper / the Hugging
Face ``GPT2LMHeadModel`` forward (pre-LN blocks, learned positions, full
causal multi-head attention, tied lm head) with ONE departure, listed in
the configuration files: the exact erf GELU where the published model uses
the tanh form (``gelu_new``), because the program under test has no tanh
form and the reference must compute what the program claims to compute.

The only thing shared with the program is the NAMES of the weights
(``gpt.h<i>.attn.q.w`` ...): the benchmark hands this module a
``get(name) -> array`` callable over the same seed-made weights. Weights
are cast to float32 one layer at a time ("blockwise"), so a bf16 model
that fills the chip never needs a second full copy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG = -1e30


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, w, n_head: int, eps: float):
    """One pre-LN transformer block. x [B, T, D] float32; w: this layer's
    16 arrays under their short names, any dtype."""
    w = {k: _f32(v) for k, v in w.items()}
    B, T, D = x.shape
    hd = D // n_head
    h = _ln(x, w["ln1.scale"], w["ln1.bias"], eps)
    q = (h @ w["attn.q.w"] + w["attn.q.b"]).reshape(B, T, n_head, hd)
    k = (h @ w["attn.k.w"] + w["attn.k.b"]).reshape(B, T, n_head, hd)
    v = (h @ w["attn.v.w"] + w["attn.v.b"]).reshape(B, T, n_head, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    pos = jnp.arange(T)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, _NEG)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, D)
    x = x + o @ w["attn.proj.w"] + w["attn.proj.b"]
    h = _ln(x, w["ln2.scale"], w["ln2.bias"], eps)
    h = jax.nn.gelu(h @ w["mlp.fc_in.w"] + w["mlp.fc_in.b"], approximate=False)
    return x + h @ w["mlp.fc_out.w"] + w["mlp.fc_out.b"]


_LAYER_KEYS = ("ln1.scale", "ln1.bias", "attn.q.w", "attn.q.b", "attn.k.w",
               "attn.k.b", "attn.v.w", "attn.v.b", "attn.proj.w", "attn.proj.b",
               "ln2.scale", "ln2.bias", "mlp.fc_in.w", "mlp.fc_in.b",
               "mlp.fc_out.w", "mlp.fc_out.b")


@jax.jit
def _embed(wte, wpe, tokens):
    T = tokens.shape[1]
    return _f32(wte)[tokens] + _f32(wpe)[:T][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_at(x, g, b, wte, positions, eps: float):
    """Logits [B, P, V] at the given positions [B, P] of hidden x."""
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _ln(xs, _f32(g), _f32(b), eps) @ _f32(wte).T


@functools.partial(jax.jit, static_argnames=("eps",))
def _nll_sum(x, g, b, wte, labels, eps: float):
    logits = _ln(x, _f32(g), _f32(b), eps) @ _f32(wte).T  # [B, T, V]
    picked = jnp.take_along_axis(logits, labels[:, :, None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def hidden(get, tokens, n_layer: int, n_head: int, eps: float = 1e-5):
    """Final residual stream [B, T, D] (before the last LayerNorm)."""
    with jax.default_matmul_precision("highest"):
        x = _embed(get("gpt.wte"), get("gpt.wpe"), tokens)
        for i in range(n_layer):
            w = {k: get(f"gpt.h{i}.{k}") for k in _LAYER_KEYS}
            x = _block(x, w, n_head=n_head, eps=eps)
    return x


def logits_at(get, tokens, positions, n_layer: int, n_head: int,
              eps: float = 1e-5):
    """Next-token logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, T] (teacher-forced: position p sees tokens 0..p)."""
    x = hidden(get, tokens, n_layer, n_head, eps)
    with jax.default_matmul_precision("highest"):
        return _logits_at(x, get("gpt.lnf.scale"), get("gpt.lnf.bias"),
                          get("gpt.wte"), positions, eps=eps)


def mean_nll(get, tokens, labels, n_layer: int, n_head: int,
             eps: float = 1e-5, chunk: int = 4, place=None) -> float:
    """Mean next-token negative log-likelihood over a batch, evaluated
    ``chunk`` sequences at a time (the [chunk*T, V] float32 logits are the
    largest array it ever holds). ``place`` puts a chunk's arrays where
    the caller wants them (several chips evaluate a chunk data-parallel;
    the arithmetic per sequence is unchanged)."""
    total, n = 0.0, 0
    for lo in range(0, tokens.shape[0], chunk):
        tok, lbl = tokens[lo:lo + chunk], labels[lo:lo + chunk]
        if place is not None:
            tok, lbl = place(tok), place(lbl)
        x = hidden(get, tok, n_layer, n_head, eps)
        with jax.default_matmul_precision("highest"):
            total += float(_nll_sum(x, get("gpt.lnf.scale"), get("gpt.lnf.bias"),
                                    get("gpt.wte"), lbl, eps=eps))
        n += int(tok.shape[0] * tok.shape[1])
    return total / n
