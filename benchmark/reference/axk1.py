"""Plain reference of the A.X-K1 block (``model_type`` "axk1"): latent
attention (MLA) under YaRN, one leading dense SwiGLU layer, then routed
SwiGLU experts beside a shared one, untied head.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching,
no absorbed projections: one full causal forward over ``[1, T]`` with K and
V of every head expanded from the latent. Layer by layer, with ``u =
rms(h, g_1)`` and ``m = rms(h, g_2)`` (eps 1e-6):

    h <- h + Attn_l(u);  h <- h + FF_l(m);  logits = rms(h_L, g_out) W_head

``Attn`` (H heads; ``n`` = qk_nope, ``r`` = qk_rope, ``v`` = v_head lanes)

    c_q = rms(u W_dq, g_q);            q = heads(c_q W_uq) = [q_nope n | q_rope r]
    [c_kv | k_r] = u W_dkv;            c_kv = rms(c_kv, g_kv)
    k_rope = rope(k_r)                 one a position, shared by every head
    q_rope = rope(q_rope)
    [k_nope n | v]_h = c_kv W_ukv[h]           (W_ukv is stored head by head, [H, c, n + v])
    s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_rope_h(t) . k_rope(j)) * scale
    Attn = W_o . concat_h(softmax_j<=t(s_h) v_h)
    scale = (n + r)^-0.5 * m^2,   m = 0.1 * mscale_all_dim * ln(factor) + 1

``rope`` turns lanes ``(2i, 2i + 1)`` together by ``pos * inv_freq_i`` with
YaRN's frequencies over the ``r`` rotated lanes (``d = r``):

    f_i = base^(-2i/d)
    low  = floor(d ln(L0 / (beta_fast * 2 pi)) / (2 ln base))    clamped to
    high = ceil (d ln(L0 / (beta_slow * 2 pi)) / (2 ln base))    [0, d - 1]
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (f_i / factor) * ramp_i + f_i * (1 - ramp_i)

at EVERY position; cos and sin carry ``yarn_mscale(factor, mscale) /
yarn_mscale(factor, mscale_all_dim)``.

``FF`` of the leading dense layers is ``(silu(m W_1) * (m W_3)) W_2``; of the
others

    s = sigmoid(m W_r)                                   float32, E scores
    the experts lie in n_group groups in order; a group scores the sum of
    its two largest s; the topk_group best groups are kept; the token takes
    the k largest s among the kept groups' experts
    w_e = scale_r * s_e / (sum over the k of s + 1e-20)
    FF  = sum_{e held here} w_e E_e(m) + E_shared(m),   E = SwiGLU

where ONLY the experts this chip holds (``first_held .. first_held + held -
1``, the leading axis of the stacked weights) are summed: the chip's share of
an expert-parallel layer, as the program computes it; the router is as wide
as published. ``first_held`` 0 and a stack of all E experts is the uncut
layer.

Departures from the published forward, each a READING of a key the catalog's
config leaves open (benchmark/configs/ax-k1.json, ``assumed``), none a
shortcut:

- ``topk_method: "none"`` with ``n_group`` 8 and ``topk_group`` 4 is read as
  the family's group-limited selection WITHOUT the aux-loss-free correction
  bias that ``"noaux_tc"`` would add; ``seq_aux`` is a training loss and has
  no forward term.
- The rotated lanes pair ``(2i, 2i + 1)`` (the family's interleaved
  convention); the config carries no ``rope_interleave``.
- The published code keeps activations in bfloat16; here everything is
  float32.

For memory only (a model that fills the chip leaves ~4 GB): the scores are
made for 512 query positions at a time, the dense layer's width is taken in
quarters, each expert's weights are cast to float32 as its turn comes, and
the embedding rows are gathered before the cast.

The only thing shared with the program is the NAMES (and so the shapes) of
the weights. ``matmul_dtype`` exists for the yardstick's own check: the same
forward with its matmul operands rounded to a lower precision has to come
out as NOT correct.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_NEG = -1e30
_Q_BLOCK = 512    # query positions whose scores are alive at once
_DENSE_PARTS = 4  # the dense layer's width, taken a part at a time

_ATTN_KEYS = ("attn.q_down.w", "attn.q_norm.scale", "attn.q_up.w", "attn.kv_down.w",
              "attn.kv_norm.scale", "attn.kv_up.w", "attn.proj.w")
_FF_KEYS = {"dense": ("mlp.gate.w", "mlp.up.w", "mlp.down.w"),
            "moe": ("moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w",
                    "moe.shared.gate.w", "moe.shared.up.w", "moe.shared.down.w")}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(a, g, eps):
    return a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps) * g


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, base: float, factor: float, original: int, beta_fast: float,
                  beta_slow: float):
    """(inv_freq [d / 2] as a list of floats, low, high)."""
    def correction(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        f = base ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low if high != low else 1e-3), 0.0), 1.0)
        out.append((f / factor) * ramp + f * (1.0 - ramp))
    return out, low, high


def _rope(a, inv_freq, gain: float):
    """a [T, ..., r] turned at positions 0..T-1, lanes (2i, 2i + 1) together."""
    T = a.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((T,) + (1,) * (a.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    even, odd = a[..., 0::2], a[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(a.shape)


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


def _swiglu(m, gate, up, down, mm, parts: int = 1):
    """SwiGLU of m [T, D] through ``gate``, ``up`` [D, F] and ``down`` [F,
    D], the width F taken ``parts`` equal parts at a time."""
    F = gate.shape[1]
    step = F // parts if F % parts == 0 else F
    out = jnp.zeros_like(m)
    for a in range(0, F, step):
        act = jax.nn.silu(mm(m, _f32(gate[:, a:a + step]))) * mm(m, _f32(up[:, a:a + step]))
        out = out + mm(act, _f32(down[a:a + step]))
    return out


def _attention(u, w, mm, n_head: int, kv_rank: int, nope: int, rope: int, v_dim: int, eps: float,
               inv_freq, rope_gain: float, scale: float):
    T = u.shape[0]
    c_q = _rms(mm(u, _f32(w["attn.q_down.w"])), _f32(w["attn.q_norm.scale"]), eps)
    q = mm(c_q, _f32(w["attn.q_up.w"])).reshape(T, n_head, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv_freq, rope_gain)
    ckr = mm(u, _f32(w["attn.kv_down.w"]))
    c_kv = _rms(ckr[:, :kv_rank], _f32(w["attn.kv_norm.scale"]), eps)
    k_rope = _rope(ckr[:, kv_rank:], inv_freq, rope_gain)                 # [T, r]
    w_ukv = jnp.transpose(_f32(w["attn.kv_up.w"]), (1, 0, 2)).reshape(kv_rank, n_head * (nope + v_dim))
    kv = mm(c_kv, w_ukv).reshape(T, n_head, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    pos = jnp.arange(T)
    qb = _Q_BLOCK if T % _Q_BLOCK == 0 else T

    def rows(args):
        qn, qr, q_pos = args                                              # [qb, H, n], [qb, H, r], [qb]
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope) + jnp.einsum("qhr,kr->hqk", qr, k_rope)) * scale
        s = jnp.where((q_pos[:, None] >= pos[None, :])[None], s, _NEG)
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(rows, (q_nope.reshape(T // qb, qb, n_head, nope),
                           q_rope.reshape(T // qb, qb, n_head, rope), pos.reshape(T // qb, qb)))
    return mm(o.reshape(T, n_head * v_dim), _f32(w["attn.proj.w"]))


def _experts(m, w, mm, top_k: int, n_group: int, topk_group: int, scale: float, first_held: int):
    """(FF [T, D] of the held experts and the shared one, the top-k expert
    ids [T, k] over all the router's experts) of normed hidden m [T, D]."""
    s = jax.nn.sigmoid(mm(m, _f32(w["moe.router.w"])))                    # [T, E]
    T, E = s.shape
    by_group = s.reshape(T, n_group, E // n_group)
    group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)  # the two largest
    kept = _top_k(group_score, topk_group)                                # [T, topk_group]
    open_ = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
    e_top = _top_k(jnp.where(jnp.repeat(open_, E // n_group, axis=1), s, -jnp.inf), top_k)
    s_top = jnp.take_along_axis(s, e_top, axis=-1)
    w_top = scale * s_top / (jnp.sum(s_top, axis=-1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(T)[:, None], e_top].set(w_top)
    held = w["moe.gate.w"].shape[0]
    dense = dense[:, first_held:first_held + held]                        # this chip's experts

    def one(acc, e):                                                      # every token through expert e
        gate, up, down, w_e = e
        return acc + w_e[:, None] * _swiglu(m, gate, up, down, mm), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (w["moe.gate.w"], w["moe.up.w"], w["moe.down.w"], dense.T))
    shared = _swiglu(m, w["moe.shared.gate.w"], w["moe.shared.up.w"], w["moe.shared.down.w"], mm)
    return out + shared, e_top


@functools.partial(jax.jit, static_argnames=(
    "ff", "n_head", "kv_rank", "nope", "rope", "v_dim", "top_k", "n_group", "topk_group", "eps",
    "inv_freq", "rope_gain", "attn_scale", "routed_scale", "first_held", "matmul_dtype"))
def _block(h, w, ff: str, n_head: int, kv_rank: int, nope: int, rope: int, v_dim: int, top_k: int,
           n_group: int, topk_group: int, eps: float, inv_freq: tuple, rope_gain: float,
           attn_scale: float, routed_scale: float, first_held: int, matmul_dtype=None):
    """One layer. h [T, D] float32; w: this layer's arrays under their short
    names, any dtype. Returns (h', top-k expert ids [T, k] or None)."""
    mm = _mm(matmul_dtype)
    u = _rms(h, _f32(w["ln1.scale"]), eps)
    h = h + _attention(u, w, mm, n_head, kv_rank, nope, rope, v_dim, eps, inv_freq, rope_gain,
                       attn_scale)
    m = _rms(h, _f32(w["ln2.scale"]), eps)
    if ff == "dense":
        return h + _swiglu(m, w["mlp.gate.w"], w["mlp.up.w"], w["mlp.down.w"], mm, _DENSE_PARTS), None
    y, e_top = _experts(m, w, mm, top_k, n_group, topk_group, routed_scale, first_held)
    return h + y, e_top


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_at(x, g, head, positions, eps: float):
    return _rms(x[positions], _f32(g), eps) @ _f32(head)


def hidden(get, tokens, n_layer: int, n_dense: int, n_head: int, kv_rank: int, nope: int, rope: int,
           v_dim: int, top_k: int, n_group: int, topk_group: int, routed_scale: float, yarn: dict,
           theta: float = 10000.0, eps: float = 1e-6, first_held: int = 0, matmul_dtype=None):
    """(final residual stream [T, D] of ``tokens`` [T], before the last
    norm; the top-k expert ids of every position and expert layer [T,
    L_moe, k]). ``yarn``: the configuration's ``rope_scaling`` group."""
    inv_freq, _, _ = yarn_inv_freq(rope, theta, yarn["factor"], yarn["original_max_position_embeddings"],
                                   yarn["beta_fast"], yarn["beta_slow"])
    m_all = yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
    rope_gain = yarn_mscale(yarn["factor"], yarn["mscale"]) / m_all
    attn_scale = (nope + rope) ** -0.5 * m_all * m_all
    routing = []
    with jax.default_matmul_precision("highest"):
        x = _f32(get("gpt.wte")[tokens])
        for i in range(n_layer):
            ff = "dense" if i < n_dense else "moe"
            keys = ("ln1.scale", "ln2.scale") + _ATTN_KEYS + _FF_KEYS[ff]
            x, e_top = _block(x, {k: get(f"gpt.h{i}.{k}") for k in keys}, ff=ff, n_head=n_head,
                              kv_rank=kv_rank, nope=nope, rope=rope, v_dim=v_dim, top_k=top_k,
                              n_group=n_group, topk_group=topk_group, eps=eps,
                              inv_freq=tuple(inv_freq), rope_gain=rope_gain, attn_scale=attn_scale,
                              routed_scale=routed_scale, first_held=first_held,
                              matmul_dtype=matmul_dtype)
            if e_top is not None:
                routing.append(e_top)
    return x, (jnp.stack(routing, axis=1) if routing else None)


def logits_at(get, tokens, positions, **kw):
    """(next-token logits [1, P, V] at ``positions`` [1, P] of ``tokens``
    [1, T], teacher-forced: position p sees tokens 0..p, through the untied
    head; the routing of :func:`hidden` as [1, T, L_moe, k])."""
    if tokens.shape[0] != 1:
        raise ValueError("the reference takes one sequence at a time: no batching")
    eps = kw.get("eps", 1e-6)
    x, routing = hidden(get, tokens[0], **kw)
    with jax.default_matmul_precision("highest"):
        logits = _logits_at(x, get("gpt.lnf.scale"), get("gpt.lm_head.w"), positions[0], eps=eps)
    return logits[None], None if routing is None else routing[None]
